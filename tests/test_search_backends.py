"""Cross-backend parity for the unified ``repro.search`` engine.

The ``numpy`` backend is the reference (exact DiskANN GreedySearch
semantics); ``jax`` and ``pallas`` must land within 2 recall@10 points of
it on both query topologies, and the stats double-count fix for the split
path is pinned on a tiny fixture.  Centroid routing (``nprobe``) must be a
pure pruning of the full scatter: ``nprobe=n_shards`` returns identical ids
on every backend, and ``nprobe=2`` over the ScaleGANN replicated shards
halves the distance budget while holding recall@10 >= 0.95.
"""

import dataclasses

import numpy as np
import pytest

from repro.configs.base import IndexConfig
from repro.core import builder
from repro.data.synthetic import make_clustered, recall_at
from repro.search import (MergedTopology, SearchStats, ShardTopology,
                          as_topology, available_backends, beam_search,
                          get_backend, register_backend, search)

BACKENDS = ("numpy", "jax", "pallas")


@pytest.fixture(scope="module")
def ds():
    return make_clustered(2000, 32, n_queries=30, spread=1.0, seed=7)


@pytest.fixture(scope="module")
def cfg():
    return IndexConfig(n_clusters=4, degree=16, build_degree=32,
                       block_size=512)


@pytest.fixture(scope="module")
def merged(ds, cfg):
    return builder.build_scalegann(ds.data, cfg, n_workers=2)


@pytest.fixture(scope="module")
def split(ds, cfg):
    return builder.build_extended_cagra(ds.data, cfg, n_workers=2)


@pytest.fixture(scope="module")
def routed_topo(ds, cfg):
    """Routing fixture: the ScaleGANN partition's replicated shards over 8
    clusters — enough shards that pruning matters, and bounded replication
    keeps boundary neighbors reachable from a routed subset."""
    b = builder.build_scalegann(
        ds.data, dataclasses.replace(cfg, n_clusters=8), n_workers=2
    )
    return b.shard_topology(ds.data)


@pytest.fixture(scope="module")
def routed_queries(ds):
    """256 held-out queries over the same 2k vectors (the module ``ds`` has
    only 30 — too few to pin a recall floor tightly)."""
    big = make_clustered(2000, 32, n_queries=256, spread=1.0, seed=7)
    np.testing.assert_array_equal(big.data, ds.data)
    return big


@pytest.fixture(scope="module")
def merged_recalls(ds, merged):
    topo = MergedTopology(data=ds.data, index=merged.index)
    out = {}
    for b in BACKENDS:
        ids, st = search(topo, ds.queries, 10, backend=b, width=64)
        out[b] = (recall_at(ids, ds.gt, 10), st)
    return out


@pytest.fixture(scope="module")
def split_recalls(ds, split):
    topo = ShardTopology(data=ds.data,
                         shard_ids=[s.ids for s in split.shards],
                         shard_graphs=split.shard_graphs)
    out = {}
    for b in BACKENDS:
        ids, st = search(topo, ds.queries, 10, backend=b, width=32)
        out[b] = (recall_at(ids, ds.gt, 10), st)
    return out


@pytest.mark.parametrize("backend", BACKENDS[1:])
def test_merged_recall_parity(merged_recalls, backend):
    """jax/pallas within 2 recall@10 points of the numpy reference."""
    ref, _ = merged_recalls["numpy"]
    got, _ = merged_recalls[backend]
    assert got >= ref - 0.02, f"{backend}: {got:.3f} vs numpy {ref:.3f}"


@pytest.mark.parametrize("backend", BACKENDS[1:])
def test_split_recall_parity(split_recalls, backend):
    ref, _ = split_recalls["numpy"]
    got, _ = split_recalls[backend]
    assert got >= ref - 0.02, f"{backend}: {got:.3f} vs numpy {ref:.3f}"


@pytest.mark.parametrize("backend", BACKENDS)
def test_backends_report_stats(merged_recalls, backend):
    _, st = merged_recalls[backend]
    assert st.n_distance_computations > 0
    assert st.n_hops > 0


def test_reference_recall_is_sane(merged_recalls, split_recalls):
    assert merged_recalls["numpy"][0] > 0.85
    assert split_recalls["numpy"][0] > 0.85


def test_multi_entry_seeding_beats_medoid_only(ds, merged):
    """The old jax path seeded from the medoid alone; entry_points seeding
    must not be worse (it restores navigability on merged kNN graphs)."""
    topo = MergedTopology(data=ds.data, index=merged.index)
    ids_m, _ = search(topo, ds.queries, 10, backend="jax", width=64,
                      n_entries=1)
    ids_e, _ = search(topo, ds.queries, 10, backend="jax", width=64,
                      n_entries=16)
    r_m = recall_at(ids_m, ds.gt, 10)
    r_e = recall_at(ids_e, ds.gt, 10)
    assert r_e >= r_m - 0.01


def test_split_stats_not_double_counted():
    """Regression (old ``core.search.split_search`` bug): the global
    re-rank recomputes distances already counted by the per-shard beam
    search; the stat must count them once.

    Tiny fixture: every shard small enough that beam search visits all of
    it, so the per-shard counts are exactly the shard sizes (+0 re-rank).
    """
    rng = np.random.default_rng(0)
    data = rng.normal(size=(40, 8)).astype(np.float32)
    # two shards, fully-connected ring graphs -> beam visits every vector
    ids_a = np.arange(0, 20, dtype=np.int64)
    ids_b = np.arange(20, 40, dtype=np.int64)
    graphs = []
    for n in (20, 20):
        g = np.stack([(np.arange(n) + s) % n for s in range(1, 6)], axis=1)
        graphs.append(g.astype(np.int32))
    topo = ShardTopology(data=data, shard_ids=[ids_a, ids_b],
                         shard_graphs=graphs)
    ids, st = search(topo, data[:3] + 0.01, 5, backend="numpy", width=32)
    # 3 queries x (20 + 20) vectors, each scored exactly once
    assert st.n_distance_computations == 3 * 40, st
    # and the results really are the global top-5
    d = ((data[None, :, :] - (data[:3] + 0.01)[:, None, :]) ** 2).sum(-1)
    expect = np.argsort(d, axis=1)[:, :5]
    assert set(ids[0].tolist()) == set(expect[0].tolist())


@pytest.mark.parametrize("backend", BACKENDS)
def test_routed_full_probe_matches_scatter(ds, routed_topo, backend):
    """nprobe=n_shards takes the *routed* branch (query×centroid tile,
    per-shard grouping, slot scatter-back) but covers every shard — it must
    return exactly the full-scatter ids on every backend, and cost exactly
    one routing tile more."""
    n_shards = len(routed_topo.shard_ids)
    ids_full, st_full = search(routed_topo, ds.queries, 10, backend=backend,
                               width=64)
    ids_all, st_all = search(routed_topo, ds.queries, 10, backend=backend,
                             width=64, nprobe=n_shards)
    np.testing.assert_array_equal(ids_full, ids_all)
    assert (st_all.n_distance_computations
            == st_full.n_distance_computations + len(ds.queries) * n_shards)


@pytest.mark.parametrize("backend", ("numpy", "jax"))
def test_routed_nprobe2_recall_floor_and_distance_cut(routed_topo,
                                                      routed_queries,
                                                      backend):
    """The routing win over the replicated ScaleGANN shards: nprobe=2 cuts
    the distance budget >= 2x versus full scatter while holding
    recall@10 >= 0.95 (the pallas split driver is shared with numpy and is
    covered by the parity test above)."""
    qs = routed_queries.queries
    ids_full, st_full = search(routed_topo, qs, 10, backend=backend,
                               width=64)
    ids2, st2 = search(routed_topo, qs, 10, backend=backend, width=64,
                       nprobe=2)
    r2 = recall_at(ids2, routed_queries.gt, 10)
    assert r2 >= 0.95, f"routed recall@10 {r2:.3f}"
    cut = st_full.n_distance_computations / st2.n_distance_computations
    assert cut >= 2.0, f"distance cut {cut:.2f}x"


@pytest.mark.parametrize("backend", ("numpy", "jax"))
def test_auto_nprobe_margin_extremes_match_fixed(ds, routed_topo, backend):
    """Adaptive routing is a strict generalization of fixed nprobe: margin
    1.0 keeps only the nearest shard (== nprobe=1) and an unbounded margin
    keeps every shard (== nprobe=n_shards), id-for-id."""
    n_shards = len(routed_topo.shard_ids)
    qs = ds.queries
    ids_1, st_1 = search(routed_topo, qs, 10, backend=backend, width=64,
                         nprobe=1)
    ids_m1, st_m1 = search(routed_topo, qs, 10, backend=backend, width=64,
                           nprobe=("auto", 1.0))
    np.testing.assert_array_equal(ids_1, ids_m1)
    assert st_1.n_distance_computations == st_m1.n_distance_computations
    ids_all, _ = search(routed_topo, qs, 10, backend=backend, width=64,
                        nprobe=n_shards)
    ids_huge, _ = search(routed_topo, qs, 10, backend=backend, width=64,
                         nprobe=("auto", 1e9))
    np.testing.assert_array_equal(ids_all, ids_huge)


@pytest.mark.parametrize("backend", ("numpy", "jax"))
def test_auto_nprobe_beats_fixed_at_same_budget(routed_topo, routed_queries,
                                                backend):
    """The adaptive margin spends the probe budget where it matters
    (boundary queries fan out, easy queries stay cheap): at the default
    margin it must hold the fixed-nprobe=2 recall floor with *fewer*
    distance computations than scatter, and beat nprobe=1's recall."""
    qs = routed_queries.queries
    ids_a, st_a = search(routed_topo, qs, 10, backend=backend, width=64,
                         nprobe="auto")
    _, st_full = search(routed_topo, qs, 10, backend=backend, width=64,
                        nprobe=len(routed_topo.shard_ids))
    ids_1, _ = search(routed_topo, qs, 10, backend=backend, width=64,
                      nprobe=1)
    r_a = recall_at(ids_a, routed_queries.gt, 10)
    r_1 = recall_at(ids_1, routed_queries.gt, 10)
    assert r_a >= 0.95, f"auto recall@10 {r_a:.3f}"
    assert r_a > r_1
    assert (st_a.n_distance_computations
            < 0.5 * st_full.n_distance_computations)


@pytest.mark.parametrize("dtype", ("bf16", "uint8"))
def test_quantized_recall_within_001_on_2k_fixture(merged, routed_queries,
                                                   dtype):
    """The staged-dtype acceptance bar: quantized traversal + f32 re-rank
    must hold recall@10 within 0.01 of the f32 path on the 2k fixture
    (256 held-out queries, jax serving backend)."""
    qs = routed_queries.queries
    topo = MergedTopology(data=routed_queries.data, index=merged.index)
    ids_f, _ = search(topo, qs, 10, backend="jax", width=64)
    ids_q, st = search(topo, qs, 10, backend="jax", width=64, dtype=dtype)
    r_f = recall_at(ids_f, routed_queries.gt, 10)
    r_q = recall_at(ids_q, routed_queries.gt, 10)
    assert r_q >= r_f - 0.01, f"{dtype}: {r_q:.3f} vs f32 {r_f:.3f}"
    assert st.n_quantized_distance_computations > 0
    assert st.n_rerank_distance_computations > 0


@pytest.mark.parametrize("backend", ("numpy", "jax"))
@pytest.mark.parametrize("dtype", ("bf16", "uint8"))
def test_quantized_recall_within_001_routed(routed_topo, routed_queries,
                                            backend, dtype):
    """Same bar on the centroid-routed nprobe=2 path: per-shard QuantSpecs
    + the exact pool merge must not cost more than 0.01 recall@10."""
    qs = routed_queries.queries
    ids_f, _ = search(routed_topo, qs, 10, backend=backend, width=64,
                      nprobe=2)
    ids_q, _ = search(routed_topo, qs, 10, backend=backend, width=64,
                      nprobe=2, dtype=dtype)
    r_f = recall_at(ids_f, routed_queries.gt, 10)
    r_q = recall_at(ids_q, routed_queries.gt, 10)
    assert r_q >= r_f - 0.01, f"{dtype}: {r_q:.3f} vs f32 {r_f:.3f}"


def test_parse_nprobe_specs(ds, routed_topo):
    from repro.search import parse_nprobe

    assert parse_nprobe(None)[0] == "scatter"
    assert parse_nprobe(3) == ("fixed", 3, 0.0)
    mode, _, margin = parse_nprobe("auto")
    assert mode == "auto" and margin > 1.0
    assert parse_nprobe(("auto", 2.0)) == ("auto", 0, 2.0)
    assert parse_nprobe(2.0) == ("fixed", 2, 0.0)  # integral floats pass
    for bad in (0, -1, 2.7, True, "margin", ("auto", 0.5), ("fixed", 2),
                ("auto",)):
        with pytest.raises(ValueError, match="nprobe|margin"):
            search(routed_topo, ds.queries[:1], 10, width=32, nprobe=bad)


def test_search_stamps_n_queries(ds, merged):
    ids, st = search(merged.index, ds.queries[:7], 10, data=ds.data)
    assert st.n_queries == 7
    per_q = st.per_query()
    assert per_q["distance_computations"] == pytest.approx(
        st.n_distance_computations / 7)


def test_routing_without_centroids_falls_back_to_scatter(ds, split):
    """A topology that never carried centroids cannot route — nprobe must
    silently preserve the full-scatter results."""
    topo = ShardTopology(data=ds.data,
                         shard_ids=[s.ids for s in split.shards],
                         shard_graphs=split.shard_graphs)
    assert topo.centroids is None
    ids_n, st_n = search(topo, ds.queries[:8], 10, width=32)
    ids_r, st_r = search(topo, ds.queries[:8], 10, width=32, nprobe=2)
    np.testing.assert_array_equal(ids_n, ids_r)
    assert st_n.n_distance_computations == st_r.n_distance_computations


def test_nprobe_validation(ds, split):
    with pytest.raises(ValueError, match="nprobe"):
        search(split.topology(ds.data), ds.queries[:1], 10, width=32,
               nprobe=0)


def test_shard_entries_are_centroid_nearest(routed_topo):
    """Each shard seeds from the local vector nearest its centroid."""
    entries = routed_topo.shard_entries()
    for s, ids in enumerate(routed_topo.shard_ids):
        if len(ids) == 0:
            continue
        rows = routed_topo.data[ids].astype(np.float32)
        d = ((rows - routed_topo.centroids[s][None, :]) ** 2).sum(axis=1)
        assert d[entries[s]] == pytest.approx(d.min())


@pytest.mark.parametrize("backend", BACKENDS)
def test_routed_shard_seeds_reach_every_component(backend):
    """A shard can hold parts of two clusters with no edges between them
    (capacity spills part of a cluster into a neighbour's shard).  Seeded
    from the centroid entry alone, a query in the other part never reaches
    it; ``n_entries`` stratified seeds put a seed in each part."""
    rng = np.random.default_rng(3)
    a = rng.normal(size=(40, 8)).astype(np.float32)
    b = rng.normal(size=(24, 8)).astype(np.float32) + 50.0
    data = np.concatenate([a, b])
    ring = lambda n, off: np.stack(  # noqa: E731
        [(np.arange(n) + s) % n + off for s in (1, 2, 3)], axis=1)
    graph = np.concatenate([ring(40, 0), ring(24, 40)]).astype(np.int32)
    topo = ShardTopology(data=data, shard_ids=[np.arange(64, dtype=np.int64)],
                         shard_graphs=[graph],
                         centroids=a.mean(axis=0, keepdims=True))
    q = b[:4] + 0.01
    want = np.argsort(((data[None] - q[:, None]) ** 2).sum(-1), axis=1)[:, :5]
    kw = dict(backend=backend, width=32, nprobe=1)
    one, _ = search(topo, q, 5, n_entries=1, **kw)
    many, _ = search(topo, q, 5, n_entries=16, **kw)
    assert (one < 40).all()  # stuck in the centroid entry's component
    np.testing.assert_array_equal(np.sort(many, axis=1), np.sort(want, axis=1))


@pytest.mark.parametrize("backend", BACKENDS)
def test_tiny_shard_pools_are_padded(backend):
    """Regression: a shard with fewer than k vectors returns a narrower
    per-shard pool; the split driver must pad it to k columns instead of
    relying on every shard contributing exactly k."""
    rng = np.random.default_rng(1)
    data = rng.normal(size=(23, 8)).astype(np.float32)
    ids_a = np.arange(20, dtype=np.int64)
    ids_b = np.arange(20, 23, dtype=np.int64)  # 3 < k = 5
    graph_a = np.stack([(np.arange(20) + s) % 20 for s in (1, 2, 3, 4)],
                       axis=1).astype(np.int32)
    graph_b = np.stack([(np.arange(3) + s) % 3 for s in (1, 2)],
                       axis=1).astype(np.int32)
    cents = np.stack([data[:20].mean(axis=0), data[20:].mean(axis=0)])
    topo = ShardTopology(data=data, shard_ids=[ids_a, ids_b],
                         shard_graphs=[graph_a, graph_b], centroids=cents)
    q = data[:4] + 0.01
    d = ((data[None, :, :] - q[:, None, :]) ** 2).sum(-1)
    expect = np.argsort(d, axis=1)[:, :5]
    for nprobe in (None, 1, 2):
        ids, _ = search(topo, q, 5, backend=backend, width=16, nprobe=nprobe)
        assert ids.shape == (4, 5)
        if nprobe != 1:  # full coverage -> exact global top-5
            for row, exp in zip(ids, expect):
                assert set(row.tolist()) == set(exp.tolist())


def test_ip_metric_parity(ds, merged):
    """The retrieval-attention scoring path (metric="ip") works on every
    backend and agrees with brute force on the clear winners."""
    topo = MergedTopology(data=ds.data, index=merged.index, metric="ip")
    sc = ds.data.astype(np.float32) @ ds.queries[0].astype(np.float32)
    brute = set(np.argsort(-sc)[:10].tolist())
    for b in BACKENDS:
        ids, _ = search(topo, ds.queries[:1], 10, backend=b, width=96)
        overlap = len(set(ids[0].tolist()) & brute)
        assert overlap >= 7, f"{b}: ip overlap {overlap}/10"


def test_topology_adapters(ds, merged, split):
    """Bare GlobalIndex and (ids, graphs) pairs are accepted; topologies
    pass through; junk is rejected."""
    ids_a, _ = search(merged.index, ds.queries[:4], 10, data=ds.data)
    ids_b, _ = search(MergedTopology(data=ds.data, index=merged.index),
                      ds.queries[:4], 10)
    np.testing.assert_array_equal(ids_a, ids_b)
    pair = ([s.ids for s in split.shards], split.shard_graphs)
    assert isinstance(as_topology(pair, ds.data), ShardTopology)
    with pytest.raises(ValueError):
        search(merged.index, ds.queries[:1], 10)  # data missing
    with pytest.raises(TypeError):
        as_topology(object())


@pytest.mark.parametrize("backend", BACKENDS)
def test_width_must_cover_k(ds, merged, backend):
    """Uniform contract: the candidate list bounds the result count, so
    width < k is a clear error on every backend (the old paths diverged:
    numpy over-returned, jax raised an opaque XLA shape error, pallas
    silently truncated)."""
    with pytest.raises(ValueError, match="width"):
        search(merged.index, ds.queries[:1], 100, data=ds.data,
               backend=backend, width=64)


def test_backend_registry():
    assert set(available_backends()) >= {"numpy", "jax", "pallas"}
    with pytest.raises(ValueError):
        get_backend("cuda")
    with pytest.raises(TypeError):
        register_backend("bad", object())

    class Fake:
        def search_merged(self, topo, queries, k, *, width, n_entries):
            return np.zeros((len(queries), k), np.int64), SearchStats(1, 1)

        def search_split(self, topo, queries, k, *, width, n_entries,
                         nprobe=None):
            return np.zeros((len(queries), k), np.int64), SearchStats(1, 1)

    register_backend("fake", Fake())
    try:
        assert get_backend("fake") is not None
    finally:
        import repro.search.api as api

        del api._REGISTRY["fake"]


def test_beam_search_single_query(ds, merged):
    """The exported per-query primitive (latency path) still works."""
    ids, st = beam_search(ds.data, merged.index.graph,
                          merged.index.entry_points(8), ds.queries[0], 10,
                          width=64)
    assert len(ids) == 10
    assert st.n_distance_computations > 0
    overlap = len(set(ids.tolist()) & set(ds.gt[0].tolist()))
    assert overlap >= 7
