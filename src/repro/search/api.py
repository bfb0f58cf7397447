"""Backend registry + the one public query entry point, ``search``.

Every query topology the repo serves (merged ScaleGANN/DiskANN index,
split-only shard scatter, retrieval-attention inner-product) goes through
this function; backends plug in behind a small protocol so future scaling
work (GPU-resident serving, async batching, query routing) lands as a new
backend, not a new call-site convention.
"""

from __future__ import annotations

import dataclasses
import importlib
from typing import Protocol, runtime_checkable

import numpy as np

from repro.search.types import (DEFAULT_RERANK, MergedTopology, NprobeSpec,
                                SearchStats, ShardTopology, as_topology,
                                parse_dtype, parse_nprobe)


@runtime_checkable
class SearchBackend(Protocol):
    """A search engine implementation.

    Both methods return ``(ids [Q, k] int64, SearchStats)``; unused result
    slots are -1.  Modules satisfy this protocol (the built-ins are plain
    modules exposing the two functions).  ``dtype``/``rerank`` select the
    staged-precision distance path (see :func:`search`); backends that
    share the ``run_merged``/``run_split`` drivers get it for free.
    """

    def search_merged(
        self, topo: MergedTopology, queries: np.ndarray, k: int, *,
        width: int, n_entries: int, dtype: str, rerank: int,
    ) -> tuple[np.ndarray, SearchStats]: ...

    def search_split(
        self, topo: ShardTopology, queries: np.ndarray, k: int, *,
        width: int, n_entries: int, nprobe: NprobeSpec, dtype: str,
        rerank: int,
    ) -> tuple[np.ndarray, SearchStats]: ...


# name -> backend object, or a module path string resolved lazily (keeps
# `import repro.search` from paying jax tracing costs for unused backends)
_REGISTRY: dict[str, SearchBackend | str] = {
    "numpy": "repro.search.numpy_backend",
    "jax": "repro.search.jax_backend",
    "pallas": "repro.search.pallas_backend",
}


def register_backend(name: str, backend: SearchBackend) -> None:
    """Register (or replace) a backend under ``name``."""
    if not isinstance(backend, SearchBackend):
        raise TypeError(
            "backend must expose search_merged and search_split"
        )
    _REGISTRY[name] = backend


def available_backends() -> list[str]:
    return sorted(_REGISTRY)


def get_backend(name: str) -> SearchBackend:
    try:
        entry = _REGISTRY[name]
    except KeyError:
        raise ValueError(
            f"unknown search backend {name!r}; available: "
            f"{available_backends()}"
        ) from None
    if isinstance(entry, str):
        entry = importlib.import_module(entry)
        _REGISTRY[name] = entry
    return entry


def beam_pool(
    data: np.ndarray,
    graph: np.ndarray,
    entries,
    queries: np.ndarray,
    pool: int,
    *,
    backend: str = "jax",
    n_iters: int | None = None,
    metric: str = "l2",
    n_real: int | None = None,
) -> tuple[np.ndarray, np.ndarray, SearchStats]:
    """Build-time search primitive: the engine's raw batched beam, returning
    the *whole* candidate pool per query — ``(ids [Q, pool] int64 with -1
    padding, dists [Q, pool] f32, SearchStats)``.

    Index construction (batched Vamana insertion, NN-descent-style rounds)
    needs the visited pool *and its distances*, not just a top-k — that is
    exactly the beam's final candidate list, so this runs the backend's
    beam with ``k == width == pool`` and skips the topology/re-rank layers
    of :func:`search`.  Distances are true metric values (squared L2 /
    negated inner product), directly comparable with freshly computed
    ones — what ``RobustPrune``'s α-domination test consumes.

    Every backend exposes the same hook (``beam_fn``); ``"jax"`` is the
    throughput path the batched builders default to, ``"numpy"`` the exact
    reference fallback.  Stats carry the engine's usual meaning (seed +
    fresh-neighbor scores, expanded-node hops).  ``n_real`` limits the
    stats to the first ``n_real`` queries — build rounds pad their last
    batch to a stable jit shape by cycling real points, and the padded
    lanes must not inflate the build's distance accounting (same
    convention as the routed split driver).  With ``n_real`` set, the
    returned arrays are ``[n_real, pool]`` on every backend (the padded
    lanes carry no information — they repeat real queries — and the
    backends disagree on whether to materialize them, so this function
    slices them off uniformly).
    """
    impl = get_backend(backend)
    beam = getattr(impl, "beam_fn", None)
    if beam is None:
        raise ValueError(
            f"backend {backend!r} does not expose a raw beam (beam_fn) "
            "for build-time searches"
        )
    pool = int(pool)
    if pool < 1:
        raise ValueError(f"pool must be >= 1, got {pool}")
    queries = np.asarray(queries, np.float32)
    ids, dists, stats = beam(
        data, graph, entries, queries, pool, width=pool, n_iters=n_iters,
        metric=metric, n_real=n_real,
    )
    if n_real is not None:
        ids, dists = ids[:n_real], dists[:n_real]
    stats.n_queries = len(queries) if n_real is None else n_real
    return np.asarray(ids, np.int64), np.asarray(dists, np.float32), stats


def search(
    index_or_shards,
    queries: np.ndarray,
    k: int,
    *,
    backend: str = "numpy",
    width: int = 64,
    n_entries: int = 16,
    nprobe: NprobeSpec = None,
    dtype: str = "f32",
    rerank: int = DEFAULT_RERANK,
    data: np.ndarray | None = None,
    metric: str | None = None,
) -> tuple[np.ndarray, SearchStats]:
    """Serve a query batch on any topology with any registered backend.

    ``index_or_shards`` — a :class:`MergedTopology` / :class:`ShardTopology`,
    a bare :class:`~repro.core.merge.GlobalIndex` (pass ``data``), or a
    ``(shard_ids, shard_graphs)`` pair (pass ``data``).

    ``backend`` — ``"numpy"`` (reference, exact DiskANN GreedySearch
    semantics), ``"jax"`` (vmapped batched beam, throughput-shaped) or
    ``"pallas"`` (kernel-staged distances/top-k, interpret-mode off-TPU).

    ``nprobe`` — split topologies only: route each query to its ``nprobe``
    nearest shards by partition centroid (one batched query×centroid
    distance tile, counted in the stats) instead of searching every shard.
    The default ``None`` — or a topology without centroids — preserves the
    full scatter-to-all-shards behavior; ``nprobe >= n_shards`` routes
    through the same machinery but covers every shard, returning the
    scatter ids exactly (plus the counted routing tile).  ``nprobe="auto"``
    — or ``("auto", margin)`` — adapts the probe count per query: every
    shard whose centroid distance is within ``margin`` (default
    :data:`~repro.search.types.DEFAULT_AUTO_MARGIN`) of the query's nearest
    centroid is probed.  Ignored on merged topologies (a merged graph has
    no shards to prune).

    ``n_entries`` — seeds per beam besides the center entry: the merged
    graph's medoid, or each probed shard's centroid-nearest row, plus
    ``n_entries`` rows spread evenly over the index or shard
    (:func:`repro.core.merge.entry_points`).  ``1`` seeds from the center
    entry alone.

    ``dtype`` — the staged-precision distance path (PilotANN-style: cheap
    traversal, exact finish).  ``"f32"`` (default) is bit-identical to the
    historical path.  ``"bf16"`` streams vectors as bfloat16 (half the
    memory traffic, f32 accumulation); ``"uint8"`` traverses on affine
    uint8 codes with integer-accumulated distances
    (:class:`~repro.search.QuantSpec`, learned per shard for split
    topologies).  Either staged dtype has the beam rank ``rerank·k``
    candidates (clamped to ``width``) on quantized distances, then re-ranks
    them *exactly* in f32 — the stats report the quantized/re-rank split
    via ``n_quantized_distance_computations`` /
    ``n_rerank_distance_computations``.  The quantized storage views are
    cached *on the topology object*: callers looping staged searches
    should build a topology once and reuse it (a bare ``GlobalIndex`` /
    ``(ids, graphs)`` input is adapted to a fresh topology per call, which
    re-runs the quantization data pass every time).

    Returns ``(ids [Q, k] int64, SearchStats)``; the stats are stamped with
    ``n_queries`` so callers that aggregate across calls (the
    ``repro.serving`` worker) can merge with ``+=`` and keep per-query
    averages exact.
    """
    if width < k:
        raise ValueError(
            f"width ({width}) must be >= k ({k}): the candidate list bounds "
            "how many results a beam search can return"
        )
    parse_nprobe(nprobe)  # validate the spec before any backend work
    parse_dtype(dtype)
    if isinstance(rerank, bool) or int(rerank) != rerank or rerank < 1:
        raise ValueError(
            f"rerank must be a positive int (re-rank rerank·k candidates), "
            f"got {rerank!r}"
        )
    rerank = int(rerank)
    topo = as_topology(index_or_shards, data, metric=metric or "l2")
    if metric is not None and topo.metric != metric:
        # never mutate a caller-owned topology object
        topo = dataclasses.replace(topo, metric=metric)
    impl = get_backend(backend)
    queries = np.asarray(queries, np.float32)
    from repro.telemetry import current_tracer

    tr = current_tracer()
    if tr.enabled:  # the gate keeps the untraced path allocation-free
        span = tr.span("search.engine", backend=backend,
                       n_queries=len(queries), k=k, dtype=dtype)
    else:
        span = tr.span()  # the shared no-op span
    with span:
        if isinstance(topo, MergedTopology):
            ids, stats = impl.search_merged(
                topo, queries, k, width=width, n_entries=n_entries,
                dtype=dtype, rerank=rerank,
            )
        else:
            ids, stats = impl.search_split(
                topo, queries, k, width=width, n_entries=n_entries,
                nprobe=nprobe, dtype=dtype, rerank=rerank,
            )
    stats.n_queries = len(queries)
    return ids, stats
