"""Fused beam engine (``repro.kernels.beam``) vs the jax backend: bit parity.

The fused engine's whole value proposition is "same answers, one dispatch":
candidate lists, top-k state, and visited bitmaps live in VMEM scratch for
the Pallas lowering (flat-batch XLA elsewhere), and the traversal epilogue
re-ranks in the same kernel.  That only holds if both lowerings reproduce
the jax backend's wavefront semantics *exactly* — same expand-8 ordering,
same ``lax.top_k`` (value, position) tie rule, same visited dedup — so this
suite pins **ids bit-identical** (not recall-close) against
``jax_backend.batch_beam_search`` across f32/bf16/uint8 × l2/ip for both
the XLA and interpret lowerings, and the fused re-rank epilogue against the
host ``ops.rerank_exact`` (ids, distances, and the n_scored accounting).

End-to-end, ``search(backend="pallas")`` must match ``backend="jax"`` on
ids and SearchStats for merged and split topologies at every served dtype;
the interpret lowering (the CI stand-in for the TPU kernel) is exercised
through the same ``search()`` entry point on a small fixture.

``merge_topk``/``bitonic_sort_lex`` edge cases ride along: pools smaller
than k must pad with (inf, -1), an all-visited tile (every candidate
spilled to the sentinel column N) must leave the incumbent top-k untouched,
and the lex tie rule must order equal values by ascending index with
payloads carried through the same permutation.
"""

import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import IndexConfig
from repro.core import builder
from repro.core.merge import GlobalIndex
from repro.data.synthetic import make_clustered
from repro.kernels import beam as kb
from repro.kernels import ops
from repro.kernels.topk import bitonic_sort_lex, merge_topk
from repro.search import MergedTopology, ShardTopology, search
from repro.search import jax_backend as jb
from repro.search.types import QuantSpec

# ---------------------------------------------------------------------------
# raw-kernel fixture: adversarially scruffy graph (dangling -1 edges,
# duplicate neighbors, entries scattered across the id range)
# ---------------------------------------------------------------------------

N, D, R, Q = 500, 24, 10, 17
K, WIDTH = 10, 32


@pytest.fixture(scope="module")
def fix():
    rng = np.random.default_rng(0)
    data = rng.standard_normal((N, D)).astype(np.float32)
    graph = rng.integers(0, N, (N, R)).astype(np.int32)
    graph[rng.random((N, R)) < 0.15] = -1  # dangling edges
    entries = np.array([3, 77, 200, 466], np.int64)
    queries = rng.standard_normal((Q, D)).astype(np.float32)
    return data, graph, entries, queries


def _stage(data, queries, qname):
    quant = {"f32": None, "bf16": "bf16",
             "u8": QuantSpec.from_data(data)}[qname]
    x, qv, s, zp = jb._prep_stage(data, queries, quant)
    if qname == "u8":
        qv = np.asarray(qv).astype(np.uint8)  # wrapper contract: codes
    return quant, x, qv, s, zp


LOWERINGS = ("xla", "pallas_interpret")


@pytest.mark.parametrize("lowering", LOWERINGS)
@pytest.mark.parametrize("metric", ["l2", "ip"])
@pytest.mark.parametrize("qname", ["f32", "bf16", "u8"])
def test_traversal_bit_parity(fix, qname, metric, lowering):
    """Both lowerings reproduce the jax backend's ids exactly, and the
    kernel's per-query n_dist/hops counters sum to the backend's stats."""
    data, graph, entries, queries = fix
    quant, x, qv, s, zp = _stage(data, queries, qname)
    ids, ds, stats = jb.batch_beam_search(
        data, graph, entries, queries, K, width=WIDTH, metric=metric,
        quant=quant)
    fids, fds, nd, hops, _ = kb.fused_beam(
        x, graph, jb._prep_entries(entries, WIDTH), qv, K, width=WIDTH,
        metric=metric, scale=s, zp=zp, lowering=lowering)
    np.testing.assert_array_equal(np.asarray(fids), ids)
    np.testing.assert_allclose(
        np.where(np.isfinite(fds), np.asarray(fds), 0.0),
        np.where(np.isfinite(ds), ds, 0.0), atol=2e-3, rtol=1e-4)
    assert int(np.asarray(nd).sum()) == stats.n_distance_computations
    assert int(np.asarray(hops).sum()) == stats.n_hops


@pytest.mark.parametrize("lowering", LOWERINGS)
@pytest.mark.parametrize("qname", ["bf16", "u8"])
def test_fused_rerank_matches_host_rerank(fix, qname, lowering):
    """The in-kernel exact-f32 epilogue == host ``ops.rerank_exact`` on the
    same candidate pool: ids bit-identical, distances to 1e-4, and the
    n_rerank counter equals the host's n_scored."""
    data, graph, entries, queries = fix
    quant, x, qv, s, zp = _stage(data, queries, qname)
    kq = min(4 * K, WIDTH)
    ids, _, _ = jb.batch_beam_search(
        data, graph, entries, queries, kq, width=WIDTH, quant=quant)
    rids, rds, n_scored = ops.rerank_exact(data, ids, queries, K, "l2")
    fids, fds, _, _, nrr = kb.fused_beam(
        x, graph, jb._prep_entries(entries, WIDTH), qv, kq, width=WIDTH,
        scale=s, zp=zp, x_exact=data, q_exact=queries, rerank_k=K,
        lowering=lowering)
    np.testing.assert_array_equal(np.asarray(fids).astype(np.int64), rids)
    np.testing.assert_allclose(
        np.where(np.isfinite(fds), np.asarray(fds), 0.0),
        np.where(np.isfinite(rds), rds, 0.0), atol=1e-4)
    assert int(np.asarray(nrr).sum()) == n_scored


# ---------------------------------------------------------------------------
# end-to-end: search(backend="pallas") == search(backend="jax") on ids and
# SearchStats, merged and split, every served dtype, both lowerings
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def e2e():
    """Small built index (small so the interpret lowering's per-trip
    interpreter cost stays in test budget): merged + split topologies."""
    ds = make_clustered(600, 16, n_queries=8, spread=1.0, seed=11)
    cfg = IndexConfig(n_clusters=2, degree=8, build_degree=16,
                      block_size=256)
    merged = builder.build_scalegann(ds.data, cfg, n_workers=2)
    split = builder.build_extended_cagra(ds.data, cfg, n_workers=2)
    mt = MergedTopology(data=ds.data, index=merged.index)
    st = ShardTopology(data=ds.data,
                       shard_ids=[s.ids for s in split.shards],
                       shard_graphs=split.shard_graphs)
    return ds, mt, st


def _assert_search_parity(topo, queries, dtype):
    kw = {"width": WIDTH}
    if dtype != "f32":
        kw.update(dtype=dtype, rerank=3)
    jids, jstats = search(topo, queries, K, backend="jax", **kw)
    pids, pstats = search(topo, queries, K, backend="pallas", **kw)
    np.testing.assert_array_equal(pids, jids)
    assert dataclasses.asdict(pstats) == dataclasses.asdict(jstats)


@pytest.mark.parametrize("dtype", ["f32", "bf16", "uint8"])
@pytest.mark.parametrize("topo_kind", ["merged", "split"])
def test_search_parity_xla(e2e, topo_kind, dtype):
    """CPU/auto dispatch (flat-batch XLA lowering): the serving-speed
    path must be indistinguishable from the jax backend."""
    ds, mt, st = e2e
    _assert_search_parity(mt if topo_kind == "merged" else st,
                          ds.queries, dtype)


@pytest.mark.parametrize("dtype", ["f32", "bf16", "uint8"])
@pytest.mark.parametrize("topo_kind", ["merged", "split"])
def test_search_parity_interpret(e2e, topo_kind, dtype):
    """force_interpret runs the *Pallas kernel* through the interpreter —
    this is the CI proof that the VMEM-resident kernel (not just its XLA
    twin) computes the jax backend's answers bit-for-bit."""
    ds, mt, st = e2e
    ops.set_pallas_mode("force_interpret")
    try:
        _assert_search_parity(mt if topo_kind == "merged" else st,
                              ds.queries[:4], dtype)
    finally:
        ops.set_pallas_mode("auto")


# ---------------------------------------------------------------------------
# merge_topk / bitonic_sort_lex edge cases
# ---------------------------------------------------------------------------


def test_merge_topk_pool_smaller_than_k():
    """Fewer real candidates than k: the tail must be (inf, -1) padding,
    never a fabricated id."""
    vals = jnp.array([[0.5, jnp.inf]], jnp.float32)
    idxs = jnp.array([[7, -1]], jnp.int32)
    nv = jnp.array([[0.2, jnp.inf, 0.9]], jnp.float32)
    ni = jnp.array([[3, -1, 11]], jnp.int32)
    sv, si = merge_topk(vals, idxs, nv, ni, 5)
    np.testing.assert_array_equal(np.asarray(si)[0], [3, 7, 11, -1, -1])
    got = np.asarray(sv)[0]
    np.testing.assert_allclose(got[:3], [0.2, 0.5, 0.9])
    assert np.all(np.isinf(got[3:]))


def test_merge_topk_all_visited_tile_is_identity():
    """A tile where every candidate was already visited arrives fully
    spilled — distance inf, id at the sentinel column N (masked to -1 by
    the beam's gather) — and must leave the incumbent top-k unchanged."""
    vals = jnp.array([[0.1, 0.4, 0.8]], jnp.float32)
    idxs = jnp.array([[2, 9, 4]], jnp.int32)
    nv = jnp.full((1, 6), jnp.inf, jnp.float32)
    ni = jnp.full((1, 6), -1, jnp.int32)
    sv, si = merge_topk(vals, idxs, nv, ni, 3)
    np.testing.assert_array_equal(np.asarray(si), idxs)
    np.testing.assert_allclose(np.asarray(sv), vals)


def test_bitonic_lex_tie_rule_matches_top_k():
    """tie_by_index=True must order equal values by ascending index — the
    lax.top_k tie rule the fused keep-step relies on for bit parity."""
    vals = jnp.array([[2.0, 1.0, 2.0, 1.0, 3.0, 1.0, 2.0, 0.0]])
    idxs = jnp.arange(8, dtype=jnp.int32)[None, :]
    sv, si, _ = bitonic_sort_lex(vals, idxs, tie_by_index=True)
    np.testing.assert_array_equal(np.asarray(si)[0],
                                  [7, 1, 3, 5, 0, 2, 6, 4])
    np.testing.assert_allclose(np.asarray(sv)[0],
                               [0, 1, 1, 1, 2, 2, 2, 3])


def test_bitonic_lex_payloads_ride_the_same_permutation():
    vals = jnp.array([[3.0, 1.0, 2.0, 0.0]])
    idxs = jnp.array([[10, 11, 12, 13]], jnp.int32)
    pay = jnp.array([[100, 111, 122, 133]], jnp.int32)
    sv, si, (sp,) = bitonic_sort_lex(vals, idxs, payloads=(pay,))
    np.testing.assert_array_equal(np.asarray(si)[0], [13, 11, 12, 10])
    np.testing.assert_array_equal(np.asarray(sp)[0], [133, 111, 122, 100])


def test_pallas_backend_refuses_shard_above_vmem_bound():
    """Above the compiled VMEM bound the Pallas lowering raises a named
    error (bytes and bound) instead of switching to the XLA lowering."""
    rng = np.random.default_rng(1)
    n = 90_000  # uint8 + re-rank: the model is past VMEM_LIMIT_BYTES
    assert kb.pallas_vmem_bytes(n, 128, 64, np.uint8, dx=128) \
        > kb.VMEM_LIMIT_BYTES
    data = rng.standard_normal((n, 128)).astype(np.float32)
    graph = rng.integers(0, n, (n, 64)).astype(np.int32)
    index = GlobalIndex(graph=graph, medoid=0, n_vectors=n)
    topo = MergedTopology(data=data, index=index)
    ops.set_pallas_mode("force_interpret")
    try:
        with pytest.raises(ValueError, match=r"needs \d+ bytes .* above "
                           rf"the {kb.VMEM_LIMIT_BYTES}-byte bound"):
            search(topo, data[:2], K, backend="pallas", dtype="uint8")
    finally:
        ops.set_pallas_mode("auto")
