"""Merge order-invariance (§V-C), connectivity, search quality."""

import numpy as np
import pytest

from repro.configs.base import IndexConfig
from repro.core import builder, cagra
from repro.core.merge import (BufferedShardReader, connectivity_stats,
                              merge_shard_indexes)
from repro.core.partition import Shard, partition
from repro.data.synthetic import make_clustered, recall_at
from repro.search import search


@pytest.fixture(scope="module")
def ds():
    return make_clustered(2500, 32, n_queries=30, spread=1.0, seed=7)


@pytest.fixture(scope="module")
def cfg():
    return IndexConfig(n_clusters=5, degree=16, build_degree=32,
                       block_size=512)


@pytest.fixture(scope="module")
def built(ds, cfg):
    return builder.build_scalegann(ds.data, cfg, n_workers=2)


def test_merge_is_shard_order_invariant(ds, cfg):
    """§V-C: parallel assignment makes intra-shard order nondeterministic;
    the merge must produce the same graph for any permutation."""
    part = partition(ds.data, cfg)
    idxs = [cagra.build_shard_index(ds.data[s.ids], cfg) for s in part.shards]
    merged = merge_shard_indexes(part.shards, idxs, len(ds.data), cfg.degree,
                                 data=ds.data)
    # permute rows within every shard (ids + graph rows together)
    rng = np.random.default_rng(0)
    pshards, pidxs = [], []
    for s, ix in zip(part.shards, idxs):
        perm = rng.permutation(len(s.ids))
        inv = np.argsort(perm)
        g = ix.graph[perm]
        g = np.where(g >= 0, inv[np.maximum(g, 0)], -1)  # relabel local ids
        pshards.append(Shard(ids=s.ids[perm], is_replica=s.is_replica[perm]))
        pidxs.append(cagra.ShardIndex(graph=g.astype(np.int32),
                                      n_distance_computations=0))
    merged_p = merge_shard_indexes(pshards, pidxs, len(ds.data), cfg.degree,
                                   data=ds.data)
    # same edge sets per vertex
    for a, b in zip(merged.graph, merged_p.graph):
        assert set(a[a >= 0].tolist()) == set(b[b >= 0].tolist())


def test_merged_graph_connectivity(built):
    stats = connectivity_stats(built.index)
    assert stats["reachable_fraction"] > 0.9
    assert stats["isolated"] == 0


def test_merged_recall(ds, built):
    ids, st = search(built.index, ds.queries, 10, data=ds.data, width=128)
    r = recall_at(ids, ds.gt, 10)
    assert r > 0.85, f"recall {r}"
    assert st.n_distance_computations > 0


def test_merged_beats_split_distance_budget(ds, cfg, built):
    """Paper Fig 4/5: at comparable recall the merged index needs several×
    fewer distance computations than split-only search."""
    ids_m, st_m = search(built.index, ds.queries, 10, data=ds.data,
                         width=128)
    ec = builder.build_extended_cagra(ds.data, cfg)
    ids_s, st_s = ec.search(ds.data, ds.queries, 10, width=64)
    r_m = recall_at(ids_m, ds.gt, 10)
    r_s = recall_at(ids_s, ds.gt, 10)
    assert r_m >= r_s - 0.05  # comparable recall...
    assert st_m.n_distance_computations < st_s.n_distance_computations
    # ...with a materially smaller distance budget
    ratio = st_s.n_distance_computations / st_m.n_distance_computations
    assert ratio > 1.5, f"split/merged distance ratio {ratio}"


def test_batch_search_matches_serial(ds, built):
    ids_b, _ = search(built.index, ds.queries[:8], 10, data=ds.data,
                      backend="jax", width=64)
    ids_s, _ = search(built.index, ds.queries[:8], 10, data=ds.data,
                      backend="numpy", width=64)
    # same top-1 for most queries (tie-breaking may differ)
    agree = np.mean([
        len(set(a[:10]) & set(b[:10])) / 10 for a, b in zip(ids_b, ids_s)
    ])
    assert agree > 0.7


def test_buffered_reader_state_check():
    rows = np.arange(100, dtype=np.float32).reshape(100, 1)
    r = BufferedShardReader(rows, buffer_rows=10)
    # sequential: 10 refills for 100 rows
    for i in range(100):
        assert r.get(i)[0] == i
    assert r.misses == 10
    assert r.hits == 90
    # out-of-order correctness (state check catches the miss)
    assert r.get(3)[0] == 3
    assert r.get(99)[0] == 99


def test_vamana_build_and_search(ds):
    cfg = IndexConfig(n_clusters=4, degree=16, build_degree=32)
    res = builder.build_diskann(ds.data[:600], cfg)
    gt = ds.gt  # gt computed over full data; recompute for subset
    from repro.data.synthetic import exact_ground_truth
    gt = exact_ground_truth(ds.data[:600], ds.queries, 10)
    ids, _ = search(res.index, ds.queries, 10, data=ds.data[:600],
                    width=128)
    r = recall_at(ids, gt, 10)
    assert r > 0.8, f"vamana recall {r}"
