"""The merge's edge distances on the device (``core/merge.py``).

  * The device pass gives each edge's distance to within float32 rounding
    of a float64 computation, under ``"l2"`` and ``"ip"``, at the widths of
    the build cells, for float32 and uint8 data, across padded blocks.
  * An empty edge list, a memmap input and the host path for data too
    large for the device give the graphs they should.
"""

import numpy as np
import pytest

from repro.core import cagra, merge
from repro.core.merge import _edge_distances, merge_shard_indexes
from repro.core.partition import Shard
from repro.telemetry import ManualClock, Tracer, use_tracer

BLOCK = 512  # a small block, so a test's edges span several of them


def _data(dtype, n: int, d: int, seed: int) -> np.ndarray:
    rng = np.random.default_rng(seed)
    if dtype == np.uint8:
        return rng.integers(0, 256, (n, d)).astype(np.uint8)
    return (rng.normal(size=(n, d)) + 0.3).astype(np.float32)


@pytest.mark.parametrize("dtype", [np.float32, np.uint8])
@pytest.mark.parametrize("d", [96, 200])
@pytest.mark.parametrize("metric", ["l2", "ip"])
def test_device_distances_match_float64(monkeypatch, metric, d, dtype):
    monkeypatch.setattr(merge, "_EDGE_BLOCK", BLOCK)
    n, n_edges = 700, 3 * BLOCK + 37  # not a multiple of the block
    x = _data(dtype, n, d, seed=d)
    rng = np.random.default_rng(1)
    gids = rng.integers(0, n, n_edges)
    nbrs = rng.integers(0, n, n_edges)
    got, resident, h2d = _edge_distances(x, gids, nbrs, metric)
    assert resident and got.dtype == np.float32 and got.shape == (n_edges,)
    assert h2d == x.nbytes + 2 * 4 * 4 * BLOCK
    a = x[nbrs].astype(np.float64)
    b = x[gids].astype(np.float64)
    terms = a * b if metric == "ip" else (a - b) ** 2
    want = -terms.sum(1) if metric == "ip" else terms.sum(1)
    # float32: each product and difference rounds once, a sum of D terms
    # at most D times, each by eps relative to the sum of |terms|
    eps = np.finfo(np.float32).eps
    tol = 2 * d * eps * np.abs(terms).sum(1)
    assert (np.abs(got - want) <= tol).all()
    if dtype == np.uint8:
        # integer terms whose sums stay below 2^24: exact in float32
        np.testing.assert_array_equal(got, want.astype(np.float32))


def _shards(n: int, per: int, deg: int, seed: int, empty: bool = False):
    rng = np.random.default_rng(seed)
    shards, idxs = [], []
    for _ in range(3):
        ids = np.sort(rng.choice(n, per, replace=False)).astype(np.int64)
        if empty:  # pads and self-loops only
            g = np.full((per, deg), -1, np.int32)
            g[:, 0] = np.arange(per)
        else:
            g = np.stack([rng.choice(per, deg, replace=False) for _ in ids])
        shards.append(Shard(ids=ids, is_replica=np.zeros(per, bool)))
        idxs.append(cagra.ShardIndex(graph=g.astype(np.int32),
                                     n_distance_computations=0))
    return shards, idxs


def test_empty_edge_list_gives_the_empty_graph():
    x = _data(np.float32, 300, 16, seed=0)
    shards, idxs = _shards(300, 200, 6, seed=0, empty=True)
    g = merge_shard_indexes(shards, idxs, 300, 8, data=x)
    assert g.graph.shape == (300, 8) and (g.graph == -1).all()


def test_memmap_gives_the_graph_of_the_array(tmp_path):
    x = _data(np.float32, 400, 24, seed=2)
    mm = np.memmap(tmp_path / "x.f32", np.float32, "w+", shape=x.shape)
    mm[:] = x
    mm.flush()
    shards, idxs = _shards(400, 300, 10, seed=2)
    want = merge_shard_indexes(shards, idxs, 400, 8, data=x)
    got = merge_shard_indexes(shards, idxs, 400, 8, data=mm)
    np.testing.assert_array_equal(got.graph, want.graph)
    assert got.medoid == want.medoid


def _traced_merge(x, shards, idxs, metric):
    tracer = Tracer(ManualClock())
    with use_tracer(tracer):
        g = merge_shard_indexes(shards, idxs, len(x), 8, data=x,
                                metric=metric)
    (span,) = [e for e in tracer.to_chrome()["traceEvents"]
               if e["name"] == "merge.distances"]
    return g, {k: span["args"][k] for k in ("resident", "n_edges",
                                             "h2d_bytes")}


@pytest.mark.parametrize("metric", ["l2", "ip"])
def test_data_over_half_the_device_takes_the_host_path(monkeypatch, metric):
    x = _data(np.float32, 400, 24, seed=3)
    shards, idxs = _shards(400, 300, 10, seed=3)
    on_device, args = _traced_merge(x, shards, idxs, metric)
    assert args["resident"] and args["h2d_bytes"] > x.nbytes
    ran = []
    host = merge._segment_distances

    def spy(*a, **kw):
        ran.append(True)
        return host(*a, **kw)

    monkeypatch.setattr(merge, "_segment_distances", spy)
    # the float32 copy is x.nbytes: just over half of this limit
    monkeypatch.setattr(merge, "_device_bytes_limit",
                        lambda: 2 * x.nbytes - 2)
    g, args = _traced_merge(x, shards, idxs, metric)
    assert ran and args == {"resident": False,
                            "n_edges": args["n_edges"], "h2d_bytes": 0}
    assert args["n_edges"] > 0
    # at the limit the device path runs again
    monkeypatch.setattr(merge, "_device_bytes_limit", lambda: 2 * x.nbytes)
    ran.clear()
    _, args = _traced_merge(x, shards, idxs, metric)
    assert not ran and args["resident"]
    for a, b in zip(g.graph, on_device.graph):
        assert set(a.tolist()) == set(b.tolist())
