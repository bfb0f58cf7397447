"""CAGRA-style shard index build (paper §II-A, integrated algorithm §IV).

CAGRA builds a dense k-NN graph (degree L) with accelerator matmuls, then
prunes it to degree R with *rank-based detour counting* and reverse-edge
augmentation.  Distance computation — the stage the paper offloads to cheap
accelerators — runs through ``kernels.ops.knn`` (Pallas fused
distance+bitonic-top-k on TPU, jnp oracle on CPU).

Shapes are fixed at trace time, so a shard build is a single jittable
pipeline: this is the unit of work the spot scheduler ships to an instance.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import IndexConfig
from repro.kernels import ops


@dataclasses.dataclass
class ShardIndex:
    """Graph over one shard, in *local* coordinates (row i of `graph` is the
    neighbor list of local vector i; -1 pads)."""

    graph: np.ndarray  # [n, R] int32 local ids
    n_distance_computations: int  # build-cost proxy (paper's GPU work)


# ---------------------------------------------------------------------------
# Stage 1: exact kNN graph (degree L) via blocked fused distance+top-k
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("L",))
def _drop_self(d: jax.Array, i: jax.Array, start: jax.Array, L: int):
    """Mask each row's self-match (row r of the block is vector
    ``start + r``) and keep the L nearest, ascending."""
    rows = start + jnp.arange(d.shape[0], dtype=jnp.int32)[:, None]
    d = jnp.where(i == rows, jnp.inf, d)
    order = jnp.argsort(d, axis=1)[:, :L]
    return (jnp.take_along_axis(d, order, axis=1),
            jnp.take_along_axis(i, order, axis=1))


def build_knn_graph(
    vectors: np.ndarray, L: int, *, metric: str = "l2", row_block: int = 4096
) -> tuple[np.ndarray, np.ndarray, int]:
    """Exact kNN graph: returns (nbrs [n, L], dists [n, L], n_dist_comps).

    Row-blocked so peak memory is O(row_block · n); each block is one fused
    kernel launch (brute force — CAGRA's choice for in-memory shards; the
    shard size is capped by accelerator HBM, §IV, so exact build is
    affordable and gives the best base graph).
    """
    x = jnp.asarray(vectors, jnp.float32)
    n = x.shape[0]
    k = min(L + 1, n)  # +1: the self-match is removed below
    rb = min(row_block, n)
    nbrs, dists = [], []
    for s in range(0, n, rb):
        # every block has rb query rows (the last one padded with copies
        # of row 0, dropped below): one compiled shape per shard size
        q = x[s : s + rb]
        real = q.shape[0]
        if real < rb:
            q = jnp.concatenate([q, jnp.broadcast_to(x[:1], (rb - real,
                                                              x.shape[1]))])
        d, i = _drop_self(*ops.knn(q, x, k, metric), jnp.int32(s), L)
        nbrs.append(np.asarray(i)[:real])
        dists.append(np.asarray(d)[:real])
    nbrs = np.concatenate(nbrs)
    dists = np.concatenate(dists)
    if n <= L:  # degenerate tiny shard: pad
        pad = L - (n - 1)
        nbrs = np.pad(nbrs[:, : n - 1], ((0, 0), (0, pad)), constant_values=-1)
        dists = np.pad(
            dists[:, : n - 1], ((0, 0), (0, pad)), constant_values=np.inf
        )
    return nbrs.astype(np.int32), dists.astype(np.float32), n * n


# ---------------------------------------------------------------------------
# Stage 2: CAGRA graph optimization — detour counting + reverse edges
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("metric",))
def _detour_counts(vs: jax.Array, nbr_vecs: jax.Array, nbr_dists: jax.Array,
                   metric: str = "l2"):
    """CAGRA rank: edge (u, v_j) is 'detourable' through v_i (i<j, i.e. a
    closer neighbor) when d(v_i, v_j) < d(u, v_j).  Returns [C, L] counts.

    vs: [C, D] node vectors; nbr_vecs: [C, L, D]; nbr_dists: [C, L] ascending.
    """
    if metric == "l2":
        nn = jnp.sum(nbr_vecs**2, axis=-1)
        cross = jnp.einsum("cld,cmd->clm", nbr_vecs, nbr_vecs)
        d_ij = jnp.sqrt(jnp.maximum(nn[:, :, None] + nn[:, None, :] - 2 * cross, 0.0))
    else:
        d_ij = -jnp.einsum("cld,cmd->clm", nbr_vecs, nbr_vecs)
    L = nbr_dists.shape[1]
    rank_lt = jnp.arange(L)[:, None] < jnp.arange(L)[None, :]  # i < j
    detour = (d_ij < nbr_dists[:, None, :]) & rank_lt[None]
    valid = jnp.isfinite(nbr_dists)
    return jnp.sum(detour, axis=1) + jnp.where(valid, 0, 10**6)


def _fill_reverse_loop(
    src_s: np.ndarray, starts: np.ndarray, ends: np.ndarray, n: int, half: int
) -> np.ndarray:
    """Seed-loop reference for the reverse-edge fill (one python iteration
    per node) — kept for the bit-identity parity tests and the
    ``bench_build.py`` seed-loop baseline."""
    rev = np.full((n, half), -1, np.int32)
    for v in range(n):
        cnt = min(ends[v] - starts[v], half)
        if cnt > 0:
            rev[v, :cnt] = src_s[starts[v] : starts[v] + cnt]
    return rev


def _fill_reverse(
    src_s: np.ndarray, starts: np.ndarray, ends: np.ndarray, n: int, half: int
) -> np.ndarray:
    """Vectorized reverse-edge fill over the searchsorted segment layout:
    one fancy-indexed gather instead of an O(N) python loop.  Sources are
    sorted by destination with a *stable* sort, so each destination's first
    ``half`` sources — and their order — match the loop reference exactly."""
    rev = np.full((n, half), -1, np.int32)
    if half == 0 or src_s.size == 0:
        return rev
    cnt = np.minimum(ends - starts, half)  # [n]
    cols = np.arange(half)
    take = np.minimum(starts[:, None] + cols[None, :], src_s.size - 1)
    vals = src_s[take]
    return np.where(cols[None, :] < cnt[:, None], vals, -1).astype(np.int32)


def _dedup_refill_loop(
    graph: np.ndarray, leftover: np.ndarray, R: int
) -> np.ndarray:
    """Seed-loop reference for the per-row dedup + leftover refill (python
    sets, one iteration per node) — the semantics the sort-based version is
    parity-tested against, bit for bit."""
    out_rows = graph.copy()
    for i in range(len(graph)):
        seen, out = set(), []
        for v in graph[i]:
            if v >= 0 and v != i and v not in seen:
                seen.add(v)
                out.append(v)
        if len(out) < R:
            for v in leftover[i]:
                if len(out) >= R:
                    break
                if v >= 0 and v != i and v not in seen:
                    seen.add(v)
                    out.append(v)
        out_rows[i] = out + [-1] * (R - len(out))
    return out_rows


def _dedup_refill_rows(
    graph: np.ndarray, leftover: np.ndarray, R: int
) -> np.ndarray:
    """Sort-based row dedup + refill, bit-identical to the loop reference.

    The double-``lexsort`` idiom the split re-rank uses
    (:func:`repro.search.types.rerank_shard_pools`): sort each row's
    ``graph ∪ leftover`` entries by (id, first-seen position) to collapse
    duplicates to their first occurrence, then restore first-seen order
    with a stable position sort and truncate to ``R`` — exactly the loop's
    "append first-seen valid ids, stop at R" semantics, tie-breaks
    included (first-seen position is the only tie-break either version
    uses)."""
    n = len(graph)
    ext = np.concatenate([graph, leftover], axis=1).astype(np.int64)
    if ext.shape[1] < R:  # degenerate L < R/2 configs: pad so the cap fits
        ext = np.pad(ext, ((0, 0), (0, R - ext.shape[1])),
                     constant_values=-1)
    c = ext.shape[1]
    big = np.iinfo(np.int64).max
    rows = np.arange(n)[:, None]
    key = np.where((ext < 0) | (ext == rows), big, ext)
    pos = np.broadcast_to(np.arange(c), (n, c))
    order = np.lexsort((pos, key), axis=1)  # by id, then first-seen pos
    sid = np.take_along_axis(key, order, axis=1)
    spos = np.take_along_axis(pos, order, axis=1)
    dup = np.zeros_like(sid, bool)
    dup[:, 1:] = sid[:, 1:] == sid[:, :-1]
    keep = (sid != big) & ~dup
    # restore first-seen order; dropped entries sort last
    back = np.argsort(np.where(keep, spos, c), axis=1, kind="stable")[:, :R]
    out = np.take_along_axis(np.where(keep, sid, -1), back, axis=1)
    return out.astype(graph.dtype)


def optimize_graph(
    vectors: np.ndarray,
    nbrs: np.ndarray,
    dists: np.ndarray,
    R: int,
    *,
    metric: str = "l2",
    node_block: int = 2048,
    reference: bool = False,
) -> tuple[np.ndarray, int]:
    """Prune the degree-L kNN graph to degree R: keep the R/2 forward edges
    with the fewest detours, then fill with reverse edges (CAGRA §4.2).

    ``reference=True`` runs the original per-node python loops for the
    reverse-edge fill and the row dedup/refill instead of the vectorized
    segment-scatter / sort-dedup paths — same output bit for bit
    (parity-tested), kept as the ``bench_build.py`` seed-loop baseline.
    """
    n, L = nbrs.shape
    x = vectors.astype(np.float32)
    fwd_keep = R - R // 2
    n_dist = 0
    counts = np.empty((n, L), np.int64)
    safe_nbrs = np.maximum(nbrs, 0)
    nb = min(node_block, n)
    for s in range(0, n, nb):
        e = min(s + nb, n)
        # fixed block shape (the last block repeats row n-1, dropped
        # below): one compile per shard size, not one per remainder
        rows = np.minimum(np.arange(s, s + nb), n - 1)
        c = _detour_counts(
            jnp.asarray(x[rows]),
            jnp.asarray(x[safe_nbrs[rows]]),
            jnp.asarray(dists[rows]),
            metric,
        )
        counts[s:e] = np.asarray(c)[: e - s]
        n_dist += (e - s) * L * L
    # stable: prefer fewer detours, break ties by distance rank (ascending)
    order = np.argsort(counts, axis=1, kind="stable")
    fwd = np.take_along_axis(nbrs, order[:, :fwd_keep], axis=1)  # [n, R/2]

    # reverse edges: v gains u for every kept forward edge u→v
    src = np.repeat(np.arange(n), fwd_keep)
    dst = fwd.reshape(-1)
    ok = dst >= 0
    src, dst = src[ok], dst[ok]
    order2 = np.argsort(dst, kind="stable")
    dst_s, src_s = dst[order2], src[order2]
    starts = np.searchsorted(dst_s, np.arange(n), side="left")
    ends = np.searchsorted(dst_s, np.arange(n), side="right")
    fill_rev = _fill_reverse_loop if reference else _fill_reverse
    rev = fill_rev(src_s, starts, ends, n, R // 2)

    graph = np.concatenate([fwd, rev], axis=1)  # [n, R]
    # dedup per row (forward ∪ reverse may overlap); refill from leftover kNN
    leftover = np.take_along_axis(nbrs, order[:, fwd_keep:], axis=1)
    dedup = _dedup_refill_loop if reference else _dedup_refill_rows
    graph = dedup(graph, leftover, R)
    return graph.astype(np.int32), n_dist


def build_shard_index(
    vectors: np.ndarray, cfg: IndexConfig, *, reference: bool = False
) -> ShardIndex:
    """Full CAGRA-style build of one shard (the spot-instance task body).

    ``reference=True`` routes :func:`optimize_graph` through its original
    per-node python loops (bit-identical output; the seed-loop baseline)."""
    nbrs, dists, nd1 = build_knn_graph(
        vectors, cfg.build_degree, metric=cfg.metric
    )
    graph, nd2 = optimize_graph(
        vectors, nbrs, dists, cfg.degree, metric=cfg.metric,
        reference=reference,
    )
    return ShardIndex(graph=graph, n_distance_computations=nd1 + nd2)
