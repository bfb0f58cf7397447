"""Pallas TPU kernel: tiled pairwise squared-L2 / inner-product distances.

This is the hot spot the paper accelerates on GPUs ("extensive distance
calculations ... efficiently parallelized by GPU using matmul", §II-A).  The
TPU-native formulation keeps the MXU busy with a 128×128×D block matmul and
streams HBM→VMEM row/column panels:

    ‖q − x‖² = ‖q‖² + ‖x‖² − 2·q·xᵀ

Grid: (M/bm, N/bn).  Each program loads a (bm, D) query panel and a (bn, D)
point panel into VMEM, issues one MXU matmul, and fuses the norm correction
on the VPU — one HBM round-trip per output tile.  D is padded to a lane
multiple (128) by the wrapper in ``ops.py``; zero padding does not change L2
or IP values.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# MXU-aligned default tile. (bm, D) + (bn, D) + (bm, bn) fp32 panels must fit
# VMEM (~16 MB): D=4096 → 128·4096·4·2 + 128·128·4 ≈ 4.3 MB.
BLOCK_M = 128
BLOCK_N = 128


def _distance_kernel(q_ref, x_ref, out_ref, *, metric: str):
    """f32 *and* bf16 tiles: panels are upcast at the VMEM→VREG boundary,
    so a bf16 input halves the HBM traffic while the MXU accumulates f32."""
    q = q_ref[...].astype(jnp.float32)  # [bm, D]
    x = x_ref[...].astype(jnp.float32)  # [bn, D]
    # MXU: [bm, D] @ [D, bn]
    dots = jax.lax.dot_general(
        q, x, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST,
    )
    if metric == "l2":
        qn = jnp.sum(q * q, axis=1, keepdims=True)  # [bm, 1]
        xn = jnp.sum(x * x, axis=1)[None, :]  # [1, bn]
        out_ref[...] = jnp.maximum(qn + xn - 2.0 * dots, 0.0)
    else:  # ip
        out_ref[...] = -dots


def _u8_code_dots(q_codes, x_codes):
    """Exact uint8 code dot products off an **int8 MXU matmul**.

    The MXU's native low-precision mode is int8×int8→int32; uint8 operands
    would be upcast to int32 in VREGs and lose it.  Re-centering each code
    by 128 lands in int8 exactly — on uint8 that is a bitwise ``^ 0x80``
    plus a bitcast, no widening — and the shift is undone with the code
    *sums* (one VPU reduction per panel, needed for the IP affine term
    anyway):

        Σ q·x = Σ (q−128)(x−128) + 128·(Σq + Σx) − D·128²

    Every term is integer-exact in int32 (codes ≤ 255, D ≤ 2¹⁵), so the
    result is bit-identical to the old widened-uint8 matmul.  ``D`` here is
    the *padded* width: zero-code padding contributes (0−128)² = 128² per
    padded column to the int8 product, and the constant term removes
    exactly that.

    Returns ``(dots [bm, bn] int32, sq [bm, 1] int32, sx [1, bn] int32)``.
    """
    d_pad = q_codes.shape[1]
    q8 = jax.lax.bitcast_convert_type(q_codes ^ jnp.uint8(0x80), jnp.int8)
    x8 = jax.lax.bitcast_convert_type(x_codes ^ jnp.uint8(0x80), jnp.int8)
    dots8 = jax.lax.dot_general(
        q8, x8, (((1,), (1,)), ((), ())), preferred_element_type=jnp.int32
    )  # [bm, bn] int8-MXU accumulation
    sq = jnp.sum(q_codes.astype(jnp.int32), axis=1, keepdims=True)
    sx = jnp.sum(x_codes.astype(jnp.int32), axis=1)[None, :]
    dots = dots8 + 128 * (sq + sx) - d_pad * (128 * 128)
    return dots, sq, sx


def _distance_kernel_u8(q_ref, x_ref, s_ref, zp_ref, out_ref, *,
                        metric: str, d_real: int):
    """Integer-accumulated distance tile over shared-spec uint8 codes.

    The panels stream HBM→VMEM at 1 byte/element (4× less traffic than the
    f32 kernel); the matmul runs in the MXU's native int8 mode
    (:func:`_u8_code_dots`) and the affine correction runs on the VPU in
    f32.  ``scale``/``zero_point`` arrive as (1, 1) SMEM scalars so
    per-shard specs don't recompile the kernel; ``d_real`` is the
    pre-padding dimension (zero codes pad D — they cancel in L2 and
    contribute nothing to the IP sums, but the ``D·zp²`` affine term must
    use the true D).
    """
    qi = q_ref[...].astype(jnp.int32)  # [bm, D] codes
    xi = x_ref[...].astype(jnp.int32)  # [bn, D] codes
    s = s_ref[0, 0]
    dots, _, _ = _u8_code_dots(q_ref[...], x_ref[...])  # [bm, bn] exact
    if metric == "l2":
        # shared zero-point cancels: d = s²·‖cq − cx‖²
        qn = jnp.sum(qi * qi, axis=1, keepdims=True)
        xn = jnp.sum(xi * xi, axis=1)[None, :]
        d_codes = (qn + xn - 2 * dots).astype(jnp.float32)
        out_ref[...] = jnp.maximum(d_codes, 0.0) * (s * s)
    else:  # ip: q·x = s²·cq·cx + s·zp·(Σcq + Σcx) + D·zp²  (absolute score)
        zp = zp_ref[0, 0]
        sq = jnp.sum(qi, axis=1, keepdims=True).astype(jnp.float32)
        sx = jnp.sum(xi, axis=1)[None, :].astype(jnp.float32)
        out_ref[...] = -(s * s * dots.astype(jnp.float32)
                         + s * zp * (sq + sx) + d_real * zp * zp)


@functools.partial(
    jax.jit,
    static_argnames=("metric", "d_real", "block_m", "block_n", "interpret"),
)
def pairwise_distance_u8_pallas(
    cq: jax.Array,
    cx: jax.Array,
    scale: jax.Array,  # (1, 1) f32
    zero_point: jax.Array,  # (1, 1) f32
    *,
    metric: str = "l2",
    d_real: int | None = None,
    block_m: int = BLOCK_M,
    block_n: int = BLOCK_N,
    interpret: bool = False,
) -> jax.Array:
    """[M, D] × [N, D] uint8 codes → [M, N] float32 distances.  M, N, D must
    be multiples of the block/lane sizes — ``ops.pairwise_distance_u8``
    handles padding (zero codes)."""
    m, d = cq.shape
    n, _ = cx.shape
    grid = (m // block_m, n // block_n)
    return pl.pallas_call(
        functools.partial(_distance_kernel_u8, metric=metric,
                          d_real=d if d_real is None else d_real),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, d), lambda i, j: (i, 0)),
            pl.BlockSpec((block_n, d), lambda i, j: (j, 0)),
            pl.BlockSpec((1, 1), lambda i, j: (0, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1), lambda i, j: (0, 0),
                         memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        interpret=interpret,
    )(cq, cx, scale, zero_point)


@functools.partial(
    jax.jit, static_argnames=("metric", "block_m", "block_n", "interpret")
)
def pairwise_distance_pallas(
    q: jax.Array,
    x: jax.Array,
    *,
    metric: str = "l2",
    block_m: int = BLOCK_M,
    block_n: int = BLOCK_N,
    interpret: bool = False,
) -> jax.Array:
    """[M, D] × [N, D] → [M, N] float32 distances. M, N, D must be multiples
    of the block/lane sizes — ``ops.pairwise_distance`` handles padding."""
    m, d = q.shape
    n, _ = x.shape
    grid = (m // block_m, n // block_n)
    return pl.pallas_call(
        functools.partial(_distance_kernel, metric=metric),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, d), lambda i, j: (i, 0)),
            pl.BlockSpec((block_n, d), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        interpret=interpret,
    )(q, x)
