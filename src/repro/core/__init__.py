"""ScaleGANN core — the paper's contribution (partition / build / merge /
spot scheduling / cost), in JAX + numpy orchestration.

Query serving lives in :mod:`repro.search` (backend-pluggable engine).
"""

from repro.core.builder import (  # noqa: F401
    build_diskann,
    build_extended_cagra,
    build_ggnn,
    build_scalegann,
)
from repro.core.merge import GlobalIndex, merge_shard_indexes  # noqa: F401

# NOTE: `repro.core.partition` (module) intentionally not re-exported as a
# function here — it would shadow the submodule name.  Use
# ``from repro.core.partition import partition``.
