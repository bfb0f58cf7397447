"""Public kernel surface.

Callers import the dispatching ops from here (``from repro.kernels import
pairwise_distance``) instead of deep-importing ``ops``/``topk``/``beam``
module internals.  Everything re-exported below follows the repo-wide
Pallas dispatch policy (:func:`set_pallas_mode` / ``REPRO_PALLAS``):
Pallas kernels on TPU, interpret mode for CI validation, jnp/XLA
reference elsewhere.  :func:`fused_beam` is the device-resident beam
engine (see ``beam.py``) the ``pallas`` search backend serves from.

numpy-only layers (partitioning, the reference search backend) never
import this package, so jax import cost stays off their paths.
"""

from repro.kernels.beam import fused_beam
from repro.kernels.ops import (knn, pairwise_distance, pairwise_distance_u8,
                               pallas_mode, rerank_exact, set_pallas_mode)
from repro.kernels.topk import bitonic_sort_lex, merge_topk

__all__ = [
    "bitonic_sort_lex",
    "fused_beam",
    "knn",
    "merge_topk",
    "pairwise_distance",
    "pairwise_distance_u8",
    "pallas_mode",
    "rerank_exact",
    "set_pallas_mode",
]
