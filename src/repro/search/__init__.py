"""``repro.search`` — the unified, backend-pluggable query engine.

One public API, :func:`search`, serves every query topology in the repo
(merged ScaleGANN/DiskANN index, and split-only shards — centroid-routed
via ``nprobe`` or full scatter) on any registered backend:

  * ``numpy``  — reference; exact DiskANN GreedySearch semantics + stats;
  * ``jax``    — vmapped batched beam search, multi-entry seeding,
                 sorted-merge dedup, convergence early-exit;
  * ``pallas`` — traversal in JAX, distance tiles + running top-k staged
                 through ``repro.kernels`` (interpret mode off-TPU).
"""

from repro.search.api import (SearchBackend, available_backends,  # noqa: F401
                              beam_pool, get_backend, register_backend,
                              search)
from repro.search.numpy_backend import beam_search  # noqa: F401
from repro.search.types import (DEFAULT_AUTO_MARGIN,  # noqa: F401
                                DEFAULT_RERANK, SEARCH_DTYPES,
                                MergedTopology, NprobeSpec, QuantSpec,
                                SearchStats, ShardTopology, as_topology,
                                parse_dtype, parse_nprobe)

__all__ = [
    "search",
    "beam_pool",
    "SearchBackend",
    "register_backend",
    "get_backend",
    "available_backends",
    "beam_search",
    "SearchStats",
    "MergedTopology",
    "ShardTopology",
    "as_topology",
    "NprobeSpec",
    "parse_nprobe",
    "DEFAULT_AUTO_MARGIN",
    "QuantSpec",
    "parse_dtype",
    "SEARCH_DTYPES",
    "DEFAULT_RERANK",
]
