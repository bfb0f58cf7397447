"""Device-resident fused beam-search backend (the serving hot path).

This backend is a thin host shell around :func:`repro.kernels.beam
.fused_beam` — the whole traversal (and, for staged dtypes on merged
topologies, the exact re-rank too) is **one device dispatch per served
batch**.  Contrast with the ``jax`` backend, which re-enters XLA once per
batch but keeps per-query visited bitmaps vmapped (its scatter is the
measured CPU bottleneck), and with this module's previous life as
step-by-step interpret-mode validation (one kernel launch per beam
iteration).

What lives here rather than in the kernel module:

  * **Device residency.**  Storage panels, graphs and exact re-rank rows
    are moved to the device once per ``(storage, graph)`` identity and
    cached (bounded LRU keyed on object identity — safe because entries
    hold a strong reference to the host array, so its ``id`` cannot be
    recycled).  The topology layer cooperates: quantized views
    (:meth:`MergedTopology.quant_view`, :meth:`ShardTopology.shard_quant`)
    and per-shard f32 slices (:meth:`ShardTopology.shard_store`) are cached
    *on the topology*, so steady-state serving re-uses the same host
    objects call after call and this cache turns every query into pure
    compute — no host→device copies in the hot loop.
  * **The beam_fn protocol** (``fused_beam_search``) for the shared
    ``run_merged`` / ``run_split`` drivers and build-time
    :func:`repro.search.beam_pool` — numpy in/out, ``n_real`` stats
    slicing, ``quant`` staging, exactly like the jax backend's wrapper.
  * **The fused merged staged path** (``fused_beam_search.fused_merged``):
    ``run_merged`` hands the whole staged search back to us so traversal
    *and* the exact-f32 re-rank run in the one dispatch (the split driver
    keeps its host-side epilogue — pools from different shards must merge
    before the one re-rank, so there is nothing to fuse per shard).

Lowering follows the repo-wide policy (:func:`repro.kernels.ops
.pallas_mode`): the Pallas kernel on TPU, interpret mode under
``force_interpret`` (CI validates the kernel bit-for-bit against the jax
backend), and the flat-batch XLA lowering elsewhere — the configuration
that wins the served-QPS claim in BENCH_serving.json on CPU hosts.
"""

from __future__ import annotations

import dataclasses
from collections import OrderedDict

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import beam as _beam
from repro.search.jax_backend import default_n_iters
from repro.search.types import (DEFAULT_RERANK, MergedTopology, NprobeSpec,
                                QuantSpec, SearchStats, ShardTopology,
                                run_merged, run_split)

# bounded device-residency cache: big enough for a serving deployment's
# working set (a few topologies × a few dtype stages), small enough that
# abandoned topologies don't pin host+device memory forever
_CACHE_CAP = 16


@dataclasses.dataclass
class _Prepared:
    """Device-resident arrays for one (storage, graph) pair.  ``host_*``
    are strong references: they keep the keys' ``id()`` valid (numpy
    arrays are not weakref-able) and make the identity check exact."""

    host_x: object
    host_graph: object
    x: jax.Array
    graph: jax.Array


_PREP_CACHE: "OrderedDict[tuple[int, int, str], _Prepared]" = OrderedDict()


def _prepared(data, graph, quant) -> _Prepared:
    """Device arrays for ``(data, graph)`` under a staging mode, LRU-cached
    on host-object identity.

    The stage tag is part of the key because the same host array prepares
    differently per stage (``None`` casts to f32).  Identity (not equality)
    is the right key: topologies cache their storage views, so repeat calls
    present the same objects, and an ``is`` check on the stored reference
    makes ``id`` collisions impossible.
    """
    stage = ("u8" if isinstance(quant, QuantSpec)
             else "bf16" if quant == "bf16" else "f32")
    key = (id(data), id(graph), stage)
    hit = _PREP_CACHE.get(key)
    if hit is not None and hit.host_x is data and hit.host_graph is graph:
        _PREP_CACHE.move_to_end(key)
        return hit
    if stage == "u8":
        x = jnp.asarray(np.asarray(data))  # uint8 codes
    elif stage == "bf16":
        x = jnp.asarray(data)
    else:
        x = jnp.asarray(np.asarray(data, np.float32))
    entry = _Prepared(
        host_x=data, host_graph=graph, x=x,
        graph=jnp.asarray(np.asarray(graph), jnp.int32),
    )
    _PREP_CACHE[key] = entry
    while len(_PREP_CACHE) > _CACHE_CAP:
        _PREP_CACHE.popitem(last=False)
    return entry


def _prep_queries(queries, quant):
    """(q_dev, scale, zp) for one distance stage — the query-side half of
    the jax backend's ``_prep_stage`` (uint8 queries stay *codes*; both
    lowerings widen on device)."""
    if isinstance(quant, QuantSpec):
        q = jnp.asarray(quant.quantize(queries))
        return q, jnp.float32(quant.scale), jnp.float32(quant.zero_point)
    if quant == "bf16":
        q = jnp.asarray(np.asarray(queries, np.float32)).astype(
            jnp.bfloat16)
    else:
        q = jnp.asarray(np.asarray(queries, np.float32))
    return q, jnp.float32(0), jnp.float32(0)


def _prep_entries(entries, width: int) -> jax.Array:
    e = np.atleast_1d(np.asarray(entries, np.int64))[:width]
    return jnp.asarray(e.astype(np.int32))


def fused_beam_search(
    data: np.ndarray,
    graph: np.ndarray,
    entries,
    queries: np.ndarray,
    k: int,
    *,
    width: int = 64,
    n_iters: int | None = None,
    expand: int = 8,
    metric: str = "l2",
    n_real: int | None = None,
    quant=None,
) -> tuple[np.ndarray, np.ndarray, SearchStats]:
    """The beam_fn protocol over the fused engine: numpy in/out, stats
    summed over the batch.

    ``n_real`` — count stats over the first ``n_real`` queries only (the
    routed split driver pads query groups to stable jit shapes by cycling
    real rows; padded lanes must not inflate the stats).  ``quant`` stages
    the distances (None / ``"bf16"`` / :class:`QuantSpec`) exactly like
    the jax backend; the traversal itself is one device dispatch.
    """
    n_iters = default_n_iters(width) if n_iters is None else n_iters
    prep = _prepared(data, graph, quant)
    q, scale, zp = _prep_queries(queries, quant)
    ids, ds, n_dist, hops, _ = _beam.fused_beam(
        prep.x, prep.graph, _prep_entries(entries, width), q, k,
        width=width, n_iters=n_iters, expand=expand, metric=metric,
        scale=scale, zp=zp,
    )
    nd = int(np.asarray(n_dist)[:n_real].sum())
    stats = SearchStats(
        n_distance_computations=nd,
        n_hops=int(np.asarray(hops)[:n_real].sum()),
        n_quantized_distance_computations=nd if quant is not None else 0,
    )
    return np.asarray(ids, np.int64), np.asarray(ds), stats


def _fused_merged_staged(
    topo: MergedTopology,
    entries,
    queries: np.ndarray,
    k: int,
    kq: int,
    *,
    width: int,
    n_iters: int | None,
    dtype: str,
) -> tuple[np.ndarray, SearchStats]:
    """Staged merged search with the re-rank fused into the traversal
    dispatch: the batch traverses on the quantized view, re-scores its top
    ``kq`` candidates against the device-resident exact f32 rows, and only
    the final ``[Q, k]`` ids return to host.  Same ids and stats as the
    driver's beam + :func:`repro.kernels.ops.rerank_exact` composition."""
    n_iters = default_n_iters(width) if n_iters is None else n_iters
    store, spec = topo.quant_view(dtype)
    quant = spec if spec is not None else dtype
    prep = _prepared(store, topo.index.graph, quant)
    exact = _prepared(topo.data, topo.index.graph, None)  # f32 rows
    q, scale, zp = _prep_queries(queries, quant)
    qf = jnp.asarray(np.asarray(queries, np.float32))
    ids, _, n_dist, hops, n_rr = _beam.fused_beam(
        prep.x, prep.graph, _prep_entries(entries, width), q, kq,
        width=width, n_iters=n_iters, metric=topo.metric,
        scale=scale, zp=zp,
        x_exact=exact.x, q_exact=qf, rerank_k=k,
    )
    nd = int(np.asarray(n_dist).sum())
    nrr = int(np.asarray(n_rr).sum())
    stats = SearchStats(
        n_distance_computations=nd + nrr,
        n_hops=int(np.asarray(hops).sum()),
        n_quantized_distance_computations=nd,
        n_rerank_distance_computations=nrr,
    )
    return np.asarray(ids, np.int64), stats


# run_merged hands staged merged searches back through this hook so the
# re-rank fuses into the traversal dispatch (see the driver)
fused_beam_search.fused_merged = _fused_merged_staged

# raw batched-beam hook for build-time searches (`repro.search.beam_pool`)
beam_fn = fused_beam_search


def search_merged(
    topo: MergedTopology,
    queries: np.ndarray,
    k: int,
    *,
    width: int = 64,
    n_entries: int = 16,
    n_iters: int | None = None,
    dtype: str = "f32",
    rerank: int = DEFAULT_RERANK,
) -> tuple[np.ndarray, SearchStats]:
    return run_merged(fused_beam_search, topo, queries, k, width=width,
                      n_entries=n_entries, n_iters=n_iters, dtype=dtype,
                      rerank=rerank)


def search_split(
    topo: ShardTopology,
    queries: np.ndarray,
    k: int,
    *,
    width: int = 64,
    n_entries: int = 16,
    n_iters: int | None = None,
    nprobe: NprobeSpec = None,
    dtype: str = "f32",
    rerank: int = DEFAULT_RERANK,
) -> tuple[np.ndarray, SearchStats]:
    return run_split(fused_beam_search, topo, queries, k, width=width,
                     n_entries=n_entries, n_iters=n_iters, nprobe=nprobe,
                     bucket=True, dtype=dtype, rerank=rerank)
