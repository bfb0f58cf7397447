"""A run whose timed path is broken underneath comes out not correct.

Each cell is driven through the harness (without the look for a chip) with
one fault planted where the program produces its result: a step that
returns its state unchanged, half of the batch left out, an answer altered.
A single chip has no exchange between chips to leave out.
"""

import dataclasses

import numpy as np
import pytest

from bench import run
from bench.tests.tiny import IP_CELL, TINY, case

SEED = 987654321


def _serving_fault(kind, search):
    first = {}

    def faulty(topo, queries, k, **kw):
        if kind == "half_left_out":
            keep = max(1, len(queries) // 2)
            ids, st = search(topo, queries[:keep], k, **kw)
            return np.resize(ids, (len(queries), ids.shape[1])), st
        ids, st = search(topo, queries, k, **kw)
        if kind == "state_unchanged":
            first.setdefault("ids", ids[:1].copy())
            return np.repeat(first["ids"], len(queries), axis=0), st
        ids = np.array(ids)
        ids[:, [0, -1]] = ids[:, [-1, 0]]  # altered: best and worst swapped
        return ids, st

    return faulty


def _build_fault(kind, monkeypatch):
    import repro.core.builder as builder

    if kind == "half_left_out":
        part = builder.partition

        def half(*a, **kw):
            res = part(*a, **kw)
            return dataclasses.replace(res, shards=res.shards[::2])

        monkeypatch.setattr(builder, "partition", half)
        return
    merge = builder.merge_shard_indexes

    def faulty(*a, **kw):
        idx = merge(*a, **kw)
        g = (np.full_like(idx.graph, -1) if kind == "state_unchanged"
             else np.roll(idx.graph, 1, axis=0))  # altered: rows shifted
        return dataclasses.replace(idx, graph=g)

    monkeypatch.setattr(builder, "merge_shard_indexes", faulty)


@pytest.mark.parametrize("fault", ["state_unchanged", "half_left_out",
                                   "answer_altered"])
@pytest.mark.parametrize("cell", ["deep-f32.build", "bigann-u8.routed.closed",
                                  "deep-f32.merged.closed", IP_CELL])
def test_fault_is_not_correct(cell, fault, monkeypatch):
    cell, overrides = case(cell)
    if cell.endswith(".build"):
        _build_fault(fault, monkeypatch)
    else:
        import repro.serving.server as server

        monkeypatch.setattr(server, "search",
                            _serving_fault(fault, server.search))
    r = run.run_cell(cell, SEED, 1.0, False, require_tpu=False,
                     persistent_cache=False, overrides=overrides)
    assert r["correct"] is False, r["checks"]


def test_build_knn_below_stated_precision_is_not_correct(monkeypatch):
    """The shard builds' kNN on bfloat16 vectors: the searched answers can
    still pass, the graphs' first neighbours do not."""
    import jax.numpy as jnp

    from repro.kernels import ops

    knn = ops.knn

    def bf16_knn(q, x, k, *a, **kw):
        return knn(q.astype(jnp.bfloat16).astype(jnp.float32),
                   x.astype(jnp.bfloat16).astype(jnp.float32), k, *a, **kw)

    monkeypatch.setattr(ops, "knn", bf16_knn)
    r = run.run_cell("deep-f32.build", SEED, 1.0, False, require_tpu=False,
                     persistent_cache=False, overrides=TINY)
    assert r["correct"] is False, r["checks"]
    assert r["checks"]["nn_gap"]["value"] > r["checks"]["nn_gap"]["limit"]
