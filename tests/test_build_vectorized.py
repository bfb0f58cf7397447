"""Build-path vectorization parity (PR 5).

Three contracts, one per vectorized hot loop:

  * CAGRA ``optimize_graph`` — the segment-scatter reverse fill and the
    sort-based row dedup are **bit-identical** to the per-node loop
    reference (ids *and* order), on random and clustered fixtures,
    including degenerate shapes (R//2 == 0, L < R); its detour counts,
    gathered on the device from the resident shard, equal those of a
    per-block host gather bit for bit.
  * Batched Vamana — same recall@10 as the sequential build within 0.01 at
    a comparable distance budget, on both engine backends; the vectorized
    RobustPrune equals the sequential prune row for row.
  * Merge — the global (gid, neighbor) segment sort preserves the
    permutation-invariance contract and matches the loop reference's id
    sets exactly (bit-identical rows when no distance cap applies).
"""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import IndexConfig
from repro.core import builder, cagra, vamana
from repro.core.merge import merge_shard_indexes
from repro.core.partition import Shard, partition
from repro.data.synthetic import (exact_ground_truth, make_clustered,
                                  recall_at)
from repro.search import beam_pool, search


@pytest.fixture(scope="module")
def ds():
    return make_clustered(900, 24, n_queries=40, spread=1.0, seed=13)


@pytest.fixture(scope="module")
def cfg():
    return IndexConfig(n_clusters=3, degree=16, build_degree=32,
                       block_size=512)


# ---------------------------------------------------------------------------
# CAGRA optimize_graph: bit-identity with the loop reference
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("seed,n,d,R,L", [
    (0, 400, 16, 8, 16),
    (1, 800, 32, 16, 32),
    (2, 250, 8, 7, 12),   # odd R: R//2 reverse slots != forward slots
    (3, 120, 4, 12, 6),   # degenerate L < R: dedup must pad, not crash
    (4, 50, 4, 1, 4),     # R == 1: no reverse slots at all (R//2 == 0)
])
def test_optimize_graph_bit_identical_random(seed, n, d, R, L):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, d)).astype(np.float32)
    nbrs, dists, _ = cagra.build_knn_graph(x, L)
    g_ref, nd_ref = cagra.optimize_graph(x, nbrs, dists, R, reference=True)
    g_vec, nd_vec = cagra.optimize_graph(x, nbrs, dists, R)
    np.testing.assert_array_equal(g_ref, g_vec)
    assert nd_ref == nd_vec


def test_optimize_graph_bit_identical_clustered(ds, cfg):
    nbrs, dists, _ = cagra.build_knn_graph(ds.data, cfg.build_degree)
    g_ref, _ = cagra.optimize_graph(ds.data, nbrs, dists, cfg.degree,
                                    reference=True)
    g_vec, _ = cagra.optimize_graph(ds.data, nbrs, dists, cfg.degree)
    np.testing.assert_array_equal(g_ref, g_vec)


def _host_gather_detour_counts(x, safe_nbrs, dists, metric, node_block):
    """The detour loop as it ran before the shard stayed on the device:
    each block's rows, neighbour vectors and distances gathered in numpy
    and uploaded, counted by the same ``_detour_counts``."""
    n, L = safe_nbrs.shape
    counts = np.empty((n, L), np.int64)
    nb = min(node_block, n)
    for s in range(0, n, nb):
        e = min(s + nb, n)
        rows = np.minimum(np.arange(s, s + nb), n - 1)
        c = cagra._detour_counts(jnp.asarray(x[rows]),
                                 jnp.asarray(x[safe_nbrs[rows]]),
                                 jnp.asarray(dists[rows]), metric)
        counts[s:e] = np.asarray(c)[: e - s]
    return counts


@pytest.mark.parametrize("metric", ["l2", "ip"])
@pytest.mark.parametrize("dtype", [np.float32, np.uint8])
@pytest.mark.parametrize("n,node_block", [
    (150, 256),  # shard smaller than one block
    (700, 256),  # last block partial (700 = 2 × 256 + 188)
])
def test_resident_detour_counts_match_host_gather(metric, dtype, n,
                                                  node_block):
    """Device-resident detour counts equal the host-gather loop's bit for
    bit, and so do the pruned graph and the distance count."""
    z = np.random.default_rng(n).normal(size=(n, 16))
    # float32 at a scale whose squared kNN distances straddle 1, where the
    # l2 counts (sqrt d_ij against squared d_uj) depend on every input
    x = (z * 0.3 if dtype == np.float32
         else np.clip(z * 40 + 128, 0, 255)).astype(dtype)
    L, R = 24, 12
    nbrs, dists, _ = cagra.build_knn_graph(x, L, metric=metric)
    safe = np.maximum(nbrs, 0)
    want = _host_gather_detour_counts(x.astype(np.float32), safe, dists,
                                      metric, node_block)
    got = cagra._count_detours(x.astype(np.float32), safe, dists, metric,
                               node_block)
    np.testing.assert_array_equal(got, want)
    graph, n_dist = cagra.optimize_graph(x, nbrs, dists, R, metric=metric,
                                         node_block=node_block)
    np.testing.assert_array_equal(graph,
                                  cagra._prune(nbrs, want, R, False))
    assert n_dist == n * L * L


def test_build_shard_index_reference_flag(ds, cfg):
    """The builder-facing entry points agree bit for bit too."""
    vecs = ds.data[:300]
    a = cagra.build_shard_index(vecs, cfg)
    b = cagra.build_shard_index(vecs, cfg, reference=True)
    np.testing.assert_array_equal(a.graph, b.graph)
    assert a.n_distance_computations == b.n_distance_computations


# ---------------------------------------------------------------------------
# Vamana: batched rounds vs sequential reference
# ---------------------------------------------------------------------------


def test_robust_prune_batch_matches_sequential(ds):
    """Row-for-row exactness of the vectorized prune: same kept ids, same
    order, same distance counting as the per-point reference."""
    rng = np.random.default_rng(5)
    data = ds.data
    for alpha in (1.0, 1.2):
        p_ids = rng.choice(len(data), size=16, replace=False)
        cand = rng.choice(len(data), size=(16, 24))
        # inject self-candidates and padding like a real pool
        cand[:, 3] = p_ids
        cand[:, 20:] = -1
        cand_d = np.where(
            cand >= 0,
            ((data[np.maximum(cand, 0)]
              - data[p_ids][:, None, :]) ** 2).sum(-1),
            np.inf,
        ).astype(np.float32)
        c_batch = [0]
        got = vamana.robust_prune_batch(
            p_ids, cand, cand_d, data, alpha, 8, c_batch
        )
        c_seq = [0]
        for b, p in enumerate(p_ids):
            valid = cand[b] >= 0
            want = vamana.robust_prune(
                int(p), cand[b][valid], cand_d[b][valid], data, alpha, 8,
                c_seq,
            )
            row = got[b]
            np.testing.assert_array_equal(row[row >= 0], want)
        assert c_batch[0] == c_seq[0]


@pytest.mark.parametrize("backend", ["jax", "numpy"])
def test_batched_vamana_recall_parity(ds, cfg, backend):
    """Recall@10 within 0.01 of the sequential build when both indexes are
    searched with the same budget, and the batched build does not spend a
    larger distance budget than the sequential one to get there."""
    vecs = ds.data[:700]
    gt = exact_ground_truth(vecs, ds.queries, 10)
    seq = vamana.build_shard_index_vamana_sequential(vecs, cfg)
    bat = vamana.build_shard_index_vamana(vecs, cfg, backend=backend)
    assert (bat.n_distance_computations
            <= 1.25 * seq.n_distance_computations)

    from repro.core.merge import GlobalIndex

    recalls = {}
    for name, idx in (("seq", seq), ("batched", bat)):
        gi = GlobalIndex(graph=idx.graph, medoid=0, n_vectors=len(vecs))
        ids, _ = search(gi, ds.queries, 10, data=vecs, width=64)
        recalls[name] = recall_at(ids, gt, 10)
    assert recalls["batched"] >= recalls["seq"] - 0.01, recalls


def test_batched_vamana_pad_to_is_inert(ds, cfg):
    """Row padding exists purely for jit-shape sharing: padded and unpadded
    builds produce the same graph."""
    vecs = ds.data[:300]
    a = vamana.build_shard_index_vamana(vecs, cfg, backend="numpy")
    b = vamana.build_shard_index_vamana(vecs, cfg, backend="numpy",
                                        pad_to=512)
    np.testing.assert_array_equal(a.graph, b.graph)
    assert a.n_distance_computations == b.n_distance_computations


def test_beam_pool_matches_search_topk(ds, cfg):
    """The build-time pool's best-k prefix agrees with the serving path on
    the same graph (same engine, same beam) for the numpy reference."""
    vecs = ds.data[:300]
    idx = cagra.build_shard_index(vecs, cfg)
    from repro.core.merge import GlobalIndex

    gi = GlobalIndex(graph=idx.graph, medoid=0, n_vectors=len(vecs))
    q = ds.queries[:8]
    pool_ids, pool_d, stats = beam_pool(
        vecs, idx.graph, 0, q, 32, backend="numpy"
    )
    assert pool_ids.shape == (8, 32) and pool_d.shape == (8, 32)
    assert stats.n_queries == 8
    assert stats.n_distance_computations > 0
    ids, _ = search(gi, q, 10, data=vecs, width=32, n_entries=1)
    # the pool is sorted ascending; its head must be the serving top-k
    np.testing.assert_array_equal(np.sort(pool_ids[:, :10]), np.sort(ids))
    # distances are true squared-L2 values, reusable by RobustPrune
    d_true = ((vecs[np.maximum(pool_ids, 0)] - q[:, None, :]) ** 2).sum(-1)
    valid = pool_ids >= 0
    np.testing.assert_allclose(pool_d[valid], d_true[valid], rtol=1e-4)


# ---------------------------------------------------------------------------
# Merge: segment sort vs loop reference
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def merge_inputs(ds, cfg):
    part = partition(ds.data, cfg)
    idxs = [cagra.build_shard_index(ds.data[s.ids], cfg)
            for s in part.shards]
    return part, idxs


def test_merge_matches_loop_reference(ds, cfg, merge_inputs):
    part, idxs = merge_inputs
    for data in (ds.data, None):
        ref = merge_shard_indexes(part.shards, idxs, len(ds.data),
                                  cfg.degree, data=data, reference=True)
        vec = merge_shard_indexes(part.shards, idxs, len(ds.data),
                                  cfg.degree, data=data)
        assert ref.medoid == vec.medoid
        if data is None:
            # no distance cap: first-seen order, bit-identical
            np.testing.assert_array_equal(ref.graph, vec.graph)
        else:
            # distance-capped: same id set per row (under-capacity rows
            # order by distance instead of first-seen — documented)
            for a, b in zip(ref.graph, vec.graph):
                assert set(a[a >= 0].tolist()) == set(b[b >= 0].tolist())


def test_merge_segment_sort_is_permutation_invariant(ds, cfg, merge_inputs):
    """§V-C contract against the *new* implementation: permuting rows
    within every shard leaves the merged edge sets unchanged."""
    part, idxs = merge_inputs
    merged = merge_shard_indexes(part.shards, idxs, len(ds.data),
                                 cfg.degree, data=ds.data)
    rng = np.random.default_rng(3)
    pshards, pidxs = [], []
    for s, ix in zip(part.shards, idxs):
        perm = rng.permutation(len(s.ids))
        inv = np.argsort(perm)
        g = ix.graph[perm]
        g = np.where(g >= 0, inv[np.maximum(g, 0)], -1)
        pshards.append(Shard(ids=s.ids[perm], is_replica=s.is_replica[perm]))
        pidxs.append(cagra.ShardIndex(graph=g.astype(np.int32),
                                      n_distance_computations=0))
    merged_p = merge_shard_indexes(pshards, pidxs, len(ds.data), cfg.degree,
                                   data=ds.data)
    for a, b in zip(merged.graph, merged_p.graph):
        assert set(a[a >= 0].tolist()) == set(b[b >= 0].tolist())


def test_reference_build_flag_end_to_end(ds, cfg):
    """build_scalegann(reference=True) wires the seed-loop paths and still
    produces an index of the same search quality class."""
    sub = ds.data[:500]
    gt = exact_ground_truth(sub, ds.queries, 10)
    ref = builder.build_scalegann(sub, cfg, algo="cagra", reference=True)
    vec = builder.build_scalegann(sub, cfg, algo="cagra")
    # cagra shard builds are bit-identical across the flag; the merged
    # rows carry the same edge sets (under-capacity rows may order by
    # distance instead of first-seen — the documented difference)
    for a, b in zip(ref.shard_graphs, vec.shard_graphs):
        np.testing.assert_array_equal(a, b)
    for a, b in zip(ref.index.graph, vec.index.graph):
        assert set(a[a >= 0].tolist()) == set(b[b >= 0].tolist())
    ids, _ = search(vec.index, ds.queries, 10, data=sub, width=64)
    assert recall_at(ids, gt, 10) > 0.8
