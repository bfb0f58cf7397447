"""The build's stages at the benchmark configurations' shapes
(``ann_shapes``), each checked against a plain float64 reference.

Each stage is held to what it promises, not to the values it happens to
give today: the exact kNN graph drops the self-match and nothing else,
pruning keeps degree R from kNN and reverse edges only, the partition
keeps each vector in 1..ω shards that include its nearest centroid (or
that centroid's shard is full), the merge keeps the R best of the union
of a row's shard rows, and exact ground truth is exact.
"""

import functools

import numpy as np
import pytest

from ann_shapes import (N, SHAPES, assert_distinct_rows, build, config,
                        dataset, dist64, slack)
from repro.core import cagra
from repro.core.partition import mips_lift, partition
from repro.data.synthetic import exact_ground_truth

N_KNN = 2000  # rows of the kNN-graph and pruning checks
L = 128  # the build's kNN degree


@functools.cache
def _knn_graph(name: str):
    x = dataset(name).data[:N_KNN]
    return cagra.build_knn_graph(x, L, metric=SHAPES[name][2],
                                 row_block=512)


# ---------------------------------------------------------------------------
# exact kNN graph (k = L + 1, then the self-match dropped)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("rows", ["distinct", "duplicated"])
@pytest.mark.parametrize("name", SHAPES)
def test_knn_graph_excludes_only_self(name, rows):
    metric = SHAPES[name][2]
    x = dataset(name).data[:N_KNN]
    if rows == "duplicated":  # rows 2i and 2i + 1 are exact twins
        x = np.repeat(x[: N_KNN // 2], 2, axis=0)
        nbrs, dists, _ = cagra.build_knn_graph(x, L, metric=metric,
                                               row_block=512)
    else:
        nbrs, dists, _ = _knn_graph(name)
    n = len(x)
    own = np.arange(n)[:, None]
    assert nbrs.shape == dists.shape == (n, L)
    assert nbrs.min() >= 0 and nbrs.max() < n
    assert (nbrs != own).all(), "a row lists itself"
    assert_distinct_rows(nbrs)
    assert (np.diff(dists, axis=1) >= 0).all(), "distances not ascending"

    d64 = dist64(x, x, metric)
    np.fill_diagonal(d64, np.inf)  # brute force without self
    want = np.sort(d64, axis=1)[:, :L]
    room = slack(x, x, metric)
    assert (np.abs(dists - want) <= room).all()
    assert (np.abs(np.take_along_axis(d64, nbrs, axis=1) - dists)
            <= room).all()
    if rows == "duplicated":
        twin = own[:, 0] ^ 1
        # the twin is kept wherever brute force ranks it inside the L
        ranked = d64[np.arange(n), twin] < want[:, -1] - room[:, 0]
        if metric == "l2":  # at distance 0 it always is
            assert ranked.all()
        kept = (nbrs == twin[:, None]).any(axis=1)
        assert kept[ranked].all(), "an exact duplicate was dropped"


# ---------------------------------------------------------------------------
# CAGRA pruning: detour order, reverse edges, dedup and refill
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("R", [32, 64])
@pytest.mark.parametrize("name", SHAPES)
def test_optimize_graph_invariants(name, R):
    x = dataset(name).data[:N_KNN]
    nbrs, dists, _ = _knn_graph(name)
    graph, _ = cagra.optimize_graph(x, nbrs, dists, R,
                                    metric=SHAPES[name][2])
    n = len(x)
    assert graph.shape == (n, R)
    assert graph.min() >= 0 and graph.max() < n, "degree below R"
    assert (graph != np.arange(n)[:, None]).all(), "self-loop"
    assert_distinct_rows(graph)
    in_knn = np.zeros((n, n), bool)
    in_knn[np.repeat(np.arange(n), L), nbrs.reshape(-1)] = True
    v = np.repeat(np.arange(n), R)
    u = graph.reshape(-1)
    # u is v's kNN neighbour, or v is u's (a reverse edge)
    assert (in_knn[v, u] | in_knn[u, v]).all(), "an edge from nowhere"


# ---------------------------------------------------------------------------
# partition: selective replication under capacity
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("omega", [1, 2])
@pytest.mark.parametrize("name", SHAPES)
def test_partition_invariants_by_shape(name, omega):
    metric = SHAPES[name][2]
    data = dataset(name).data
    cfg = config(name, omega)
    res = partition(data, cfg)
    assert len(res.shards) == cfg.n_clusters
    member = np.zeros((N, cfg.n_clusters), bool)
    for c, shard in enumerate(res.shards):
        assert len(np.unique(shard.ids)) == len(shard.ids)
        assert len(shard.ids) <= res.state.capacity
        member[shard.ids, c] = True
    counts = member.sum(axis=1)
    assert counts.min() >= 1 and counts.max() <= omega
    # the nearest centroid, in the space assigned in (lifted under "ip")
    space = mips_lift(data) if metric == "ip" else data
    d = np.sqrt(np.maximum(dist64(space, res.state.centroids, "l2"), 0.0))
    nearest = d <= d.min(axis=1, keepdims=True) * (1 + 1e-5)
    full = np.array([len(s.ids) == res.state.capacity for s in res.shards])
    # capacity comes first: a vector may miss its nearest shard only
    # when that shard filled up
    placed = (member & nearest).any(axis=1) | (nearest & full).any(axis=1)
    assert placed.all(), "a vector is in no shard of its nearest centroid"


# ---------------------------------------------------------------------------
# merge: edge union, dedup and degree cap by the metric
# ---------------------------------------------------------------------------


def _by_row(gids: np.ndarray, nbrs: np.ndarray, d: np.ndarray):
    """Edges sorted by (row, distance), with each edge's rank in its row."""
    order = np.lexsort((d, gids))
    g, n_, d = gids[order], nbrs[order], d[order]
    idx = np.arange(len(g))
    start = np.ones(len(g), bool)
    start[1:] = g[1:] != g[:-1]
    rank = idx - np.maximum.accumulate(np.where(start, idx, 0))
    return g, n_, d, rank


@pytest.mark.parametrize("omega", [1, 2])
@pytest.mark.parametrize("name", SHAPES)
def test_merge_invariants_by_shape(name, omega):
    metric = SHAPES[name][2]
    data = dataset(name).data
    b = build(name, omega)
    R = config(name, omega).degree
    graph = b.index.graph
    assert graph.shape == (N, R)
    valid = graph >= 0
    # -1 pads only after a row's last neighbour
    assert (valid[:, 1:] <= valid[:, :-1]).all()
    assert graph.max() < N
    assert not (graph == np.arange(N)[:, None]).any(), "self-loop"
    s = np.sort(np.where(valid, graph, -1 - np.arange(R)), axis=1)
    assert (s[:, 1:] != s[:, :-1]).all(), "duplicate neighbour"

    # the plain reference: the union of every shard row of each vector
    gu, nu = [], []
    for shard, g in zip(b.shards, b.shard_graphs):
        ok = g >= 0
        gu.append(np.repeat(shard.ids, g.shape[1])[ok.reshape(-1)])
        nu.append(shard.ids[g[ok]])
    key = np.unique(np.concatenate(gu).astype(np.int64) * N
                    + np.concatenate(nu))
    gu, nu = key // N, key % N
    keep = gu != nu
    gu, nu = gu[keep], nu[keep]
    x = np.asarray(data, np.float64)

    def edge_d(g, n_):
        if metric == "ip":
            return -(x[g] * x[n_]).sum(1)
        return ((x[g] - x[n_]) ** 2).sum(1)

    rg, _, rd, rrank = _by_row(gu, nu, edge_d(gu, nu))
    want = rrank < R
    mg = np.repeat(np.arange(N), R)[valid.reshape(-1)]
    mn = graph[valid].astype(np.int64)
    assert np.isin(mg * N + mn, key).all(), "an edge no shard row holds"
    assert (np.bincount(mg, minlength=N)
            == np.bincount(rg[want], minlength=N)).all(), \
        "a row keeps fewer than min(R, |union|) neighbours"
    g2, _, d2, _ = _by_row(mg, mn, edge_d(mg, mn))
    assert (g2 == rg[want]).all()
    # the kept distances are the R best (ties at the cap may swap ids)
    room = slack(data, data, metric)[g2, 0]
    assert (np.abs(d2 - rd[want]) <= room).all(), \
        "a row does not keep the R best of its union"


# ---------------------------------------------------------------------------
# exact ground truth
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("name", SHAPES)
def test_exact_ground_truth_matches_float64(name):
    metric = SHAPES[name][2]
    ds = dataset(name)
    gt = exact_ground_truth(ds.data, ds.queries, 10, metric)
    assert gt.shape == (len(ds.queries), 10)
    assert gt.min() >= 0 and gt.max() < N
    assert_distinct_rows(gt)
    d64 = dist64(ds.queries, ds.data, metric)
    want = np.sort(d64, axis=1)[:, :10]
    got = np.take_along_axis(d64, gt, axis=1)
    assert (np.abs(got - want) <= slack(ds.queries, ds.data, metric)).all()
