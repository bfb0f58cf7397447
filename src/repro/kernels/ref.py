"""Pure-jnp oracles for every Pallas kernel in this package.

These are the ground truth the kernel tests ``assert_allclose`` against, and
the CPU execution path for benchmarks (the container has no TPU).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

# f32 products at full precision: a TPU's default f32 matmul rounds its
# operands to bf16, and the oracles are exact references
_EXACT = jax.lax.Precision.HIGHEST


# ---------------------------------------------------------------------------
# Pairwise distances
# ---------------------------------------------------------------------------


def pairwise_l2(q: jax.Array, x: jax.Array) -> jax.Array:
    """Squared L2 distances. q: [M, D], x: [N, D] -> [M, N] (float32)."""
    q = q.astype(jnp.float32)
    x = x.astype(jnp.float32)
    qn = jnp.sum(q * q, axis=-1, keepdims=True)  # [M, 1]
    xn = jnp.sum(x * x, axis=-1)[None, :]  # [1, N]
    d = qn + xn - 2.0 * jnp.matmul(q, x.T, precision=_EXACT)
    return jnp.maximum(d, 0.0)


def pairwise_ip(q: jax.Array, x: jax.Array) -> jax.Array:
    """Negative inner product (so that smaller == closer). -> [M, N]."""
    return -jnp.matmul(q.astype(jnp.float32), x.astype(jnp.float32).T,
                       precision=_EXACT)


def pairwise_distance(q, x, metric: str = "l2") -> jax.Array:
    if metric == "l2":
        return pairwise_l2(q, x)
    if metric == "ip":
        return pairwise_ip(q, x)
    raise ValueError(f"unknown metric {metric!r}")


# ---------------------------------------------------------------------------
# Quantized distances (uint8 affine codes, integer accumulation)
# ---------------------------------------------------------------------------


def pairwise_l2_u8(cq: jax.Array, cx: jax.Array, scale) -> jax.Array:
    """Squared L2 from shared-spec uint8 codes: ``scale² · ‖cq − cx‖²``.

    Both operands carry codes from the *same* affine spec
    ``x ≈ zero_point + scale·code``, so the zero-point cancels and the whole
    distance is one int32-accumulated code matmul — the MXU shape the
    Pallas kernel uses.  cq: [M, D] uint8, cx: [N, D] uint8 → [M, N] f32.
    """
    qi = cq.astype(jnp.int32)
    xi = cx.astype(jnp.int32)
    qn = jnp.sum(qi * qi, axis=-1, keepdims=True)  # [M, 1]
    xn = jnp.sum(xi * xi, axis=-1)[None, :]  # [1, N]
    d_codes = qn + xn - 2 * (qi @ xi.T)  # exact int32
    s = jnp.asarray(scale, jnp.float32)
    return jnp.maximum(d_codes.astype(jnp.float32), 0.0) * (s * s)


def pairwise_ip_u8(
    cq: jax.Array, cx: jax.Array, scale, zero_point, d_real: int
) -> jax.Array:
    """Negative inner product from shared-spec uint8 codes.

    With x = zp + s·c:  q·x = s²·cq·cx + s·zp·(Σcq + Σcx) + D·zp².  All
    terms are kept (not just the per-query-constant-free ones) so the score
    is an *absolute* approximation of −q·x — per-shard specs stay
    comparable after the f32 re-rank.  ``d_real`` is the unpadded dimension
    (zero-code padding contributes nothing to the sums or the dot).
    """
    qi = cq.astype(jnp.int32)
    xi = cx.astype(jnp.int32)
    s = jnp.asarray(scale, jnp.float32)
    zp = jnp.asarray(zero_point, jnp.float32)
    dots = (qi @ xi.T).astype(jnp.float32)  # [M, N] exact int32
    sq = jnp.sum(qi, axis=-1, keepdims=True).astype(jnp.float32)  # [M, 1]
    sx = jnp.sum(xi, axis=-1)[None, :].astype(jnp.float32)  # [1, N]
    return -(s * s * dots + s * zp * (sq + sx) + d_real * zp * zp)


def pairwise_distance_u8(
    cq: jax.Array, cx: jax.Array, scale, zero_point, metric: str = "l2",
    d_real: int | None = None,
) -> jax.Array:
    """Uint8-code distances matching :func:`pairwise_distance` semantics."""
    if metric == "l2":
        return pairwise_l2_u8(cq, cx, scale)
    if metric == "ip":
        return pairwise_ip_u8(cq, cx, scale, zero_point,
                              cq.shape[-1] if d_real is None else d_real)
    raise ValueError(f"unknown metric {metric!r}")


# ---------------------------------------------------------------------------
# k-NN (distance + selection)
# ---------------------------------------------------------------------------


def topk_smallest(dists: jax.Array, k: int):
    """(values, indices) of the k smallest along the last axis, ascending."""
    neg, idx = jax.lax.top_k(-dists, k)
    return -neg, idx


@functools.partial(jax.jit, static_argnames=("k", "metric"))
def knn(q: jax.Array, x: jax.Array, k: int, metric: str = "l2"):
    """Exact k nearest neighbors of each q row among x rows."""
    return topk_smallest(pairwise_distance(q, x, metric), k)


# ---------------------------------------------------------------------------
# Attention
# ---------------------------------------------------------------------------


def mha_attention(q, k, v, *, causal: bool = True, scale: float | None = None):
    """Reference multi-head attention.

    q: [B, H, S, Dh], k/v: [B, Hkv, T, Dh] with H % Hkv == 0 (GQA).
    Returns [B, H, S, Dh] in q.dtype; softmax in float32.
    """
    b, h, s, dh = q.shape
    hkv = k.shape[1]
    group = h // hkv
    scale = scale if scale is not None else dh**-0.5
    qf = q.astype(jnp.float32) * scale
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    qf = qf.reshape(b, hkv, group, s, dh)
    logits = jnp.einsum("bhgsd,bhtd->bhgst", qf, kf)
    if causal:
        t = k.shape[2]
        # query position i attends to key positions <= i + (t - s)
        mask = (jnp.arange(s)[:, None] + (t - s)) >= jnp.arange(t)[None, :]
        logits = jnp.where(mask[None, None, None], logits, -jnp.inf)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhgst,bhtd->bhgsd", probs, vf)
    return out.reshape(b, h, s, dh).astype(q.dtype)


def decode_attention(q, k_cache, v_cache, cache_len=None, scale=None):
    """One-token attention against a KV cache.

    q: [B, H, Dh]; k_cache/v_cache: [B, Hkv, T, Dh]; cache_len: [B] valid
    lengths (None -> all T valid). Returns [B, H, Dh].
    """
    b, h, dh = q.shape
    hkv, t = k_cache.shape[1], k_cache.shape[2]
    group = h // hkv
    scale = scale if scale is not None else dh**-0.5
    qf = (q.astype(jnp.float32) * scale).reshape(b, hkv, group, dh)
    logits = jnp.einsum("bhgd,bhtd->bhgt", qf, k_cache.astype(jnp.float32))
    if cache_len is not None:
        mask = jnp.arange(t)[None, :] < cache_len[:, None]  # [B, T]
        logits = jnp.where(mask[:, None, None], logits, -jnp.inf)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhgt,bhtd->bhgd", probs, v_cache.astype(jnp.float32))
    return out.reshape(b, h, dh).astype(q.dtype)


# ---------------------------------------------------------------------------
# K-means assignment (distance + argmin) — partitioning hot loop
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("metric",))
def assign_nearest(x: jax.Array, centroids: jax.Array, metric: str = "l2"):
    """(nearest_centroid_idx [N], distance [N]) for each row of x."""
    d = pairwise_distance(x, centroids, metric)
    idx = jnp.argmin(d, axis=1)
    return idx, jnp.take_along_axis(d, idx[:, None], axis=1)[:, 0]
