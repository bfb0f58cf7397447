"""Reference backend: per-query numpy best-first beam search.

Faithful to DiskANN's GreedySearch (the paper's unified query algorithm for
all four compared systems, §VI-A2): expand the closest unexpanded candidate,
add its neighbors, keep the best ``width``.  Exact semantics and exact
``SearchStats`` accounting make this the ground truth the batched backends
are parity-tested against.

Supports both metrics the repo uses: squared L2 (vector serving) and ``ip``
(negative inner product, the retrieval-attention scoring where larger dot
product == closer).
"""

from __future__ import annotations

import functools
import heapq

import numpy as np

from repro.search.types import (DEFAULT_RERANK, MergedTopology, NprobeSpec,
                                QuantSpec, SearchStats, ShardTopology,
                                run_merged, run_split)


def _score_rows(
    data: np.ndarray, ids: np.ndarray, q: np.ndarray, metric: str
) -> np.ndarray:
    """Distances (smaller == closer) between ``q`` and ``data[ids]``."""
    rows = np.asarray(data[ids], np.float32)
    if metric == "ip":
        return -(rows @ q)
    d = rows - q[None, :]
    return np.einsum("nd,nd->n", d, d)


def _make_scorer(data: np.ndarray, query: np.ndarray, metric: str, quant):
    """``score(ids) -> [n] f32`` closure for one query over one storage.

    ``quant`` selects the distance stage: ``None`` — exact f32 over
    whatever ``data`` holds (cast per gather); ``"bf16"`` — ``data`` is a
    bfloat16 copy, operands round to bf16 and accumulate in f32; a
    :class:`QuantSpec` — ``data`` is uint8 codes and distances are
    integer-accumulated in the code domain (the reference semantics the
    kernels and batched backends are parity-tested against).
    """
    if isinstance(quant, QuantSpec):
        cq = quant.quantize(query).astype(np.int64)
        s, zp = quant.scale, quant.zero_point
        d_real = cq.shape[0]
        cqn = int(cq @ cq)
        cqs = int(cq.sum())

        def score(ids):
            rows = np.asarray(data[ids], np.int64)
            dots = rows @ cq
            if metric == "ip":
                return np.asarray(
                    -(s * s * dots
                      + s * zp * (cqs + rows.sum(axis=1))
                      + d_real * zp * zp),
                    np.float32,
                )
            rn = np.einsum("nd,nd->n", rows, rows)
            return np.asarray(
                (s * s) * (rn - 2 * dots + cqn), np.float32
            )

        return score
    if quant == "bf16":
        q = np.asarray(query, np.float32)
        import ml_dtypes

        qb = q.astype(ml_dtypes.bfloat16).astype(np.float32)
        return lambda ids: _score_rows(data, ids, qb, metric)
    q = np.asarray(query, np.float32)
    return lambda ids: _score_rows(data, ids, q, metric)


def beam_search(
    data: np.ndarray,
    graph: np.ndarray,
    entry: int | np.ndarray,
    query: np.ndarray,
    k: int,
    *,
    width: int = 64,
    max_hops: int = 10_000,
    metric: str = "l2",
    quant=None,
) -> tuple[np.ndarray, SearchStats]:
    """Best-first graph search with candidate list of size ``width`` (>= k).

    Returns (ids [k], stats).  ``entry`` may be a single id (DiskANN's
    medoid) or an array of ids — CAGRA seeds its search with multiple entry
    points, which is what makes a merged *kNN* graph (local edges only,
    unlike Vamana's long-range edges) navigable;
    ``GlobalIndex.entry_points`` provides them.  ``quant`` (see
    :func:`_make_scorer`) swaps the scoring stage; traversal order and
    stats semantics are identical across stages.
    """
    stats = SearchStats()
    score_ids = _make_scorer(data, query, metric, quant)
    entries = np.atleast_1d(np.asarray(entry, np.int64))
    visited: set[int] = set(entries.tolist())
    d0s = score_ids(entries)
    stats.n_distance_computations += len(entries)
    # candidate list: (dist, id)
    cand: list[tuple[float, int]] = list(
        zip(d0s.tolist(), entries.tolist())
    )
    expanded: set[int] = set()
    best: list[tuple[float, int]] = list(cand)
    while stats.n_hops < max_hops:
        # closest unexpanded candidate within the best `width`
        cand.sort()
        cand = cand[:width]
        nxt = None
        for d, v in cand:
            if v not in expanded:
                nxt = v
                break
        if nxt is None:
            break
        expanded.add(nxt)
        stats.n_hops += 1
        nbrs = graph[nxt]
        nbrs = nbrs[(nbrs >= 0)]
        fresh = np.asarray([v for v in nbrs.tolist() if v not in visited],
                           np.int64)
        if fresh.size:
            visited.update(fresh.tolist())
            ds = score_ids(fresh)
            stats.n_distance_computations += int(fresh.size)
            cand.extend(zip(ds.tolist(), fresh.tolist()))
            best.extend(zip(ds.tolist(), fresh.tolist()))
    best = heapq.nsmallest(k, set(best))
    ids = np.asarray([v for _, v in best], np.int64)
    if quant is not None:  # every score above ran in the cheap dtype
        stats.n_quantized_distance_computations = (
            stats.n_distance_computations)
    return ids, stats


def search_merged(
    topo: MergedTopology,
    queries: np.ndarray,
    k: int,
    *,
    width: int = 64,
    n_entries: int = 16,
    dtype: str = "f32",
    rerank: int = DEFAULT_RERANK,
) -> tuple[np.ndarray, SearchStats]:
    """Serve a query batch on the merged index (one CPU 'server').

    The merged driver never reads the adapter's bookkeeping dists (there
    is no pool merge), so they are switched off — the reference backend's
    cost stays exactly the beam's own scoring."""
    return run_merged(
        functools.partial(_serial_batch_beam, need_dists=False),
        topo, queries, k, width=width, n_entries=n_entries, dtype=dtype,
        rerank=rerank,
    )


def _serial_batch_beam(
    data: np.ndarray,
    graph: np.ndarray,
    entry,
    queries: np.ndarray,
    k: int,
    *,
    width: int = 64,
    n_iters: int | None = None,  # unused: the reference runs to convergence
    metric: str = "l2",
    n_real: int | None = None,
    quant=None,
    need_dists: bool = True,
) -> tuple[np.ndarray, np.ndarray, SearchStats]:
    """Batched adapter over the per-query reference :func:`beam_search`, so
    the numpy backend shares the :func:`~repro.search.types.run_merged` /
    :func:`~repro.search.types.run_split` drivers (routing, pool padding,
    dtype staging, re-rank) with the batched backends.  Shape-bucketing pad
    rows (``n_real``) are skipped outright — a serial loop gains nothing
    from stable batch shapes."""
    qs = np.asarray(queries, np.float32)[:n_real]
    out = np.full((len(qs), k), -1, np.int64)
    dists = np.full((len(qs), k), np.inf, np.float32)
    stats = SearchStats()
    for i, q in enumerate(qs):
        ids, s = beam_search(data, graph, entry, q, k, width=width,
                             metric=metric, quant=quant)
        stats += s
        out[i, : len(ids)] = ids
        if len(ids) and need_dists:
            # stage-matched scores for the split driver's pool merge;
            # these rows were scored (and counted) in-shard already, so
            # this is bookkeeping, not new distance work (the merged
            # driver ignores dists and passes need_dists=False)
            dists[i, : len(ids)] = _make_scorer(data, q, metric, quant)(ids)
    return out, dists, stats


# raw batched-beam hook for build-time searches (`repro.search.beam_pool`)
beam_fn = _serial_batch_beam


def search_split(
    topo: ShardTopology,
    queries: np.ndarray,
    k: int,
    *,
    width: int = 64,
    n_entries: int = 16,
    nprobe: NprobeSpec = None,
    dtype: str = "f32",
    rerank: int = DEFAULT_RERANK,
) -> tuple[np.ndarray, SearchStats]:
    """Split-only query path (GGNN / Extended CAGRA, §VI): route each query
    to its ``nprobe`` nearest shards (all shards when ``nprobe=None`` or the
    topology has no centroids), search them independently, then merge +
    re-rank the per-shard top-k.

    The re-rank reuses distances already computed (and counted) inside the
    per-shard beam search, so it adds *no* distance computations — the old
    ``core.search.split_search`` double-counted them, inflating the paper's
    Fig. 4/5 proxy for the split baselines.  (Staged dtypes are the
    exception by design: their f32 epilogue recomputes the candidates
    exactly and is counted separately as re-rank work.)
    """
    return run_split(_serial_batch_beam, topo, queries, k, width=width,
                     n_entries=n_entries, nprobe=nprobe, dtype=dtype,
                     rerank=rerank)
