"""The benchmark's ``guarantees`` line through the serving layers, at each
configuration's shape (``ann_shapes``): ``AnnServer`` futures answer as
``repro.search`` does, a deleted id is never answered, and a durability
snapshot reopens to the same answers.

The live layer (``LiveIndex``) serves shard topologies.  Its "merged"
case is one shard that holds every row, with the merged graph as its
graph; the tombstone check also masks the merged topology itself
(``run_merged``) with the live layer's tombstones.
"""

import asyncio
import dataclasses

import numpy as np
import pytest

from ann_shapes import (FLOOR, K, N, SHAPES, assert_distinct_rows,
                        assert_guarantee, build, config, dataset, dist64,
                        search_kwargs, topology_of)
from repro.live import LiveIndex
from repro.search import ShardTopology, search
from repro.serving import AnnServer, ServingConfig

TOPOLOGIES = ["merged", "routed"]


def live_index(name: str, topology: str) -> LiveIndex:
    b, data = build(name), dataset(name).data
    if topology == "routed":
        return LiveIndex.from_build(b, data, config(name))
    whole = ShardTopology(data=data, shard_ids=[np.arange(N)],
                          shard_graphs=[b.index.graph], metric=b.metric)
    return LiveIndex.from_topology(whole, config(name))


@pytest.mark.parametrize("backend", ["numpy", "jax"])
@pytest.mark.parametrize("topology", TOPOLOGIES)
@pytest.mark.parametrize("name", SHAPES)
def test_server_guarantee(name, topology, backend):
    cfg = ServingConfig(k=K, backend=backend, max_batch=32,
                        **search_kwargs(name, topology))

    async def serve():
        async with AnnServer(topology_of(name, topology),
                             config=cfg) as srv:
            return await asyncio.gather(
                *(srv.submit(q) for q in dataset(name).queries))

    ids = np.stack([r.ids for r in asyncio.run(serve())])
    assert_guarantee(name, ids, FLOOR[topology])


@pytest.mark.parametrize("topology", TOPOLOGIES)
@pytest.mark.parametrize("name", SHAPES)
def test_tombstones_never_returned(name, topology):
    ds = dataset(name)
    # every query's three true nearest, and a tenth of the base besides
    nearest = np.argsort(dist64(ds.queries, ds.data, SHAPES[name][2]),
                         axis=1)[:, :3]
    rng = np.random.default_rng(0)
    doomed = np.union1d(nearest, rng.choice(N, N // 10, replace=False))
    li = live_index(name, topology)
    assert li.delete_batch(doomed) == len(doomed)
    snap = li.snapshot()
    served = [snap]
    if topology == "merged":
        served.append(dataclasses.replace(build(name).topology(ds.data),
                                          tombstones=snap.tombstones))
    for topo in served:
        for backend in ("numpy", "jax"):
            ids, _ = search(topo, ds.queries, K, backend=backend,
                            **search_kwargs(name, topology))
            assert ids.min() >= 0, "fewer than k live answers"
            assert_distinct_rows(ids)
            assert not np.isin(ids, doomed).any(), "a deleted id answered"


@pytest.mark.parametrize("topology", TOPOLOGIES)
@pytest.mark.parametrize("name", SHAPES)
def test_snapshot_roundtrip_same_answers(name, topology, tmp_path):
    queries = dataset(name).queries
    li = live_index(name, topology)
    li.delete_batch(np.random.default_rng(1).choice(N, N // 20,
                                                    replace=False))
    li.save(tmp_path)
    back = LiveIndex.load(tmp_path, config(name))
    try:
        assert back.n_vectors == li.n_vectors
        np.testing.assert_array_equal(back.snapshot().tombstones,
                                      li.snapshot().tombstones)
        for backend in ("numpy", "jax"):
            before, _ = search(li.snapshot(), queries, K, backend=backend,
                               **search_kwargs(name, topology))
            after, _ = search(back.snapshot(), queries, K, backend=backend,
                              **search_kwargs(name, topology))
            np.testing.assert_array_equal(after, before)
    finally:
        li.close()
        back.close()
