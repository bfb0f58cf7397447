"""Pallas TPU kernel: fused pairwise-distance + running top-k (exact kNN).

CAGRA's GPU build keeps per-query candidate lists in registers and merges new
distance tiles with warp-level bitonic networks.  The TPU-native adaptation:

  * distance tiles come off the MXU (128×128×D block matmul, as in
    ``distance.py``);
  * the running (bm, k) candidate list lives in the output VMEM block and is
    merged with each (bm, bn) tile by a **vectorized bitonic sort network**
    operating on VREG lanes (`jnp.where` compare-exchange + XOR-block
    permutations implemented as lane rolls + select — no gather, no sort
    primitive, so it lowers on Mosaic);
  * the grid's inner dimension walks the N panels, revisiting the same output
    block (standard Pallas accumulation pattern), so each query panel's
    candidate list never leaves VMEM until the scan over N completes.

HBM traffic is therefore one read of q, one read of x, and one (bm, k) write —
the same traffic the paper's GPU kernel achieves with shared-memory staging.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BLOCK_M = 128
BLOCK_N = 128


def _next_pow2(n: int) -> int:
    return 1 << (n - 1).bit_length()


def _xor_permute(a: jax.Array, stride: int, low: jax.Array) -> jax.Array:
    """a[..., i] -> a[..., i ^ stride]: two lane rolls and a select on the
    lane's ``stride`` bit (``low`` = bit clear).  Mosaic lowers rolls and
    selects; it has no lowering for the reversal a reshape+flip needs."""
    up = jnp.roll(a, -stride, axis=-1)  # a[i + stride]
    down = jnp.roll(a, stride, axis=-1)  # a[i - stride]
    return jnp.where(low, up, down)


def bitonic_sort_lex(
    vals: jax.Array,
    idxs: jax.Array,
    payloads: tuple = (),
    *,
    tie_by_index: bool = False,
):
    """Ascending bitonic sort of (vals, idxs[, *payloads]) along the last
    axis.

    Last-axis length must be a power of two.  Pure compare-exchange network:
    O(log² L) stages of elementwise select — no data-dependent control flow,
    no gather — so it lowers on Mosaic and is the in-VMEM sort the fused
    beam kernel runs on its candidate state.

    ``tie_by_index=True`` sorts by the lexicographic key ``(val, idx)``
    instead of ``val`` alone — with distinct indices the key is a total
    order, which makes the network's output *deterministic and stable-like*
    (equal values come out in ascending index order).  That is exactly
    ``lax.top_k``'s tie rule, so the fused beam's keep step can reproduce
    the jax backend's candidate lists bit-for-bit; it is also the
    ``(distance, id)`` tie-break of the re-rank epilogues.

    ``payloads`` ride along through every compare-exchange (same permutation
    as the keys): the beam kernel carries candidate ids and expanded flags
    next to its (distance, position) sort keys.
    """
    length = vals.shape[-1]
    if length & (length - 1):
        raise ValueError(f"bitonic sort needs a power-of-two length, got {length}")
    # Traced iota (not a captured numpy constant — Pallas kernels cannot
    # close over host arrays).  Lane-shaped so it broadcasts over rows.
    iota_shape = (1,) * (vals.ndim - 1) + (length,)
    iota = jax.lax.broadcasted_iota(jnp.int32, iota_shape, vals.ndim - 1)
    payloads = list(payloads)
    n_stages = length.bit_length() - 1
    for size_exp in range(1, n_stages + 1):
        size = 1 << size_exp
        for stride_exp in range(size_exp - 1, -1, -1):
            stride = 1 << stride_exp
            up = (iota & size) == 0  # ascending run?
            i_low = (iota & stride) == 0  # lower element of its pair?
            pv = _xor_permute(vals, stride, i_low)
            pi = _xor_permute(idxs, stride, i_low)
            # bool logic, not bool-valued selects: Mosaic cannot select
            # between masks
            take_min = (i_low & up) | (~i_low & ~up)
            if tie_by_index:
                le = (vals < pv) | ((vals == pv) & (idxs <= pi))
                ge = (vals > pv) | ((vals == pv) & (idxs >= pi))
            else:
                le, ge = vals <= pv, vals >= pv
            keep = (take_min & le) | (~take_min & ge)
            vals = jnp.where(keep, vals, pv)
            idxs = jnp.where(keep, idxs, pi)
            payloads = [
                jnp.where(keep, p, _xor_permute(p, stride, i_low))
                for p in payloads
            ]
    return vals, idxs, tuple(payloads)


def bitonic_sort_pairs(vals: jax.Array, idxs: jax.Array):
    """Ascending bitonic sort of (vals, idxs) along the last axis (the
    historical two-array entry point; see :func:`bitonic_sort_lex`)."""
    vals, idxs, _ = bitonic_sort_lex(vals, idxs)
    return vals, idxs


def merge_topk(vals, idxs, new_vals, new_idxs, k: int):
    """Merge a sorted (…, k) candidate list with an unsorted (…, n) tile and
    return the new ascending top-k."""
    cat_v = jnp.concatenate([vals, new_vals], axis=-1)
    cat_i = jnp.concatenate([idxs, new_idxs], axis=-1)
    pad = _next_pow2(cat_v.shape[-1]) - cat_v.shape[-1]
    if pad:
        cat_v = jnp.pad(cat_v, [(0, 0)] * (cat_v.ndim - 1) + [(0, pad)],
                        constant_values=jnp.inf)
        cat_i = jnp.pad(cat_i, [(0, 0)] * (cat_i.ndim - 1) + [(0, pad)],
                        constant_values=-1)
    sv, si = bitonic_sort_pairs(cat_v, cat_i)
    return sv[..., :k], si[..., :k]


def _knn_kernel(n_ref, q_ref, x_ref, out_d_ref, out_i_ref, *, k, block_n,
                metric):
    j = pl.program_id(1)
    n_real = n_ref[0, 0]

    @pl.when(j == 0)
    def _init():
        out_d_ref[...] = jnp.full_like(out_d_ref, jnp.inf)
        out_i_ref[...] = jnp.full_like(out_i_ref, -1)

    q = q_ref[...].astype(jnp.float32)  # [bm, D]
    x = x_ref[...].astype(jnp.float32)  # [bn, D]
    dots = jax.lax.dot_general(
        q, x, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST,
    )
    if metric == "l2":
        qn = jnp.sum(q * q, axis=1, keepdims=True)
        xn = jnp.sum(x * x, axis=1)[None, :]
        d = jnp.maximum(qn + xn - 2.0 * dots, 0.0)
    else:
        d = -dots
    col = j * block_n + jax.lax.broadcasted_iota(jnp.int32, d.shape, 1)
    d = jnp.where(col < n_real, d, jnp.inf)  # mask padded points
    new_d, new_i = merge_topk(out_d_ref[...], out_i_ref[...], d, col, k)
    out_d_ref[...] = new_d
    out_i_ref[...] = new_i


@functools.partial(
    jax.jit,
    static_argnames=("k", "metric", "block_m", "block_n", "interpret"),
)
def knn_pallas(
    q: jax.Array,
    x: jax.Array,
    k: int,
    *,
    metric: str = "l2",
    n_real: int | None = None,
    block_m: int = BLOCK_M,
    block_n: int = BLOCK_N,
    interpret: bool = False,
):
    """Exact kNN: [M, D] queries × [N, D] points → ([M, k] dist, [M, k] idx).

    M, N, D must be block/lane aligned (``ops.knn`` pads); rows ≥ ``n_real``
    in x are treated as padding.  ``n_real`` is traced (an SMEM scalar), so
    shards of different sizes padded to one shape share one compile.
    """
    m, d = q.shape
    n, _ = x.shape
    n_real = jnp.full((1, 1), n if n_real is None else n_real, jnp.int32)
    grid = (m // block_m, n // block_n)
    return pl.pallas_call(
        functools.partial(_knn_kernel, k=k, block_n=block_n, metric=metric),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1), lambda i, j: (0, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((block_m, d), lambda i, j: (i, 0)),
            pl.BlockSpec((block_n, d), lambda i, j: (j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_m, k), lambda i, j: (i, 0)),
            pl.BlockSpec((block_m, k), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((m, k), jnp.float32),
            jax.ShapeDtypeStruct((m, k), jnp.int32),
        ],
        interpret=interpret,
    )(n_real, q, x)
