"""The benchmark's ``guarantees`` line, checked on the search path at each
configuration's shape (``ann_shapes``).

Every answer is k distinct base ids, in ascending exact distance by the
index's metric (after the f32 re-rank of a staged dtype), at a recall@10
no lower than the floor ``test_search_backends.py`` holds that topology
to: 0.85 merged, 0.95 routed.  The reference is float64 brute force.
"""

import pytest

from ann_shapes import (FLOOR, K, SHAPES, assert_guarantee, dataset,
                        search_kwargs, topology_of)
from repro.search import search


@pytest.mark.parametrize("dtype", ["f32", "bf16", "uint8"])
@pytest.mark.parametrize("backend", ["numpy", "jax"])
@pytest.mark.parametrize("topology", ["merged", "routed"])
@pytest.mark.parametrize("name", SHAPES)
def test_search_guarantee(name, topology, backend, dtype):
    ids, _ = search(topology_of(name, topology), dataset(name).queries, K,
                    backend=backend, dtype=dtype,
                    **search_kwargs(name, topology))
    assert_guarantee(name, ids, FLOOR[topology])
