"""Public jit'd wrappers over the Pallas kernels with automatic padding and
backend dispatch.

Dispatch policy (``set_pallas_mode`` / ``REPRO_PALLAS`` env var):
  * ``auto``            — Pallas on TPU, jnp reference elsewhere (this CPU
                           container always takes the reference path);
  * ``force_interpret`` — run the Pallas kernels in interpret mode (tests use
                           this to validate kernel semantics on CPU);
  * ``off``             — always the jnp reference.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import distance as _distance
from repro.kernels import ref
from repro.kernels import topk as _topk

_MODE = os.environ.get("REPRO_PALLAS", "auto")
_VALID_MODES = ("auto", "force_interpret", "off")


def set_pallas_mode(mode: str) -> None:
    global _MODE
    if mode not in _VALID_MODES:
        raise ValueError(f"mode must be one of {_VALID_MODES}")
    _MODE = mode


def pallas_mode() -> str:
    return _MODE


def _use_pallas() -> tuple[bool, bool]:
    """(use_pallas_kernel, interpret)."""
    if _MODE == "off":
        return False, False
    if _MODE == "force_interpret":
        return True, True
    return jax.default_backend() == "tpu", False


def _pad_to(a: jax.Array, axis: int, multiple: int, value=0.0) -> jax.Array:
    size = a.shape[axis]
    pad = (-size) % multiple
    if pad == 0:
        return a
    widths = [(0, 0)] * a.ndim
    widths[axis] = (0, pad)
    return jnp.pad(a, widths, constant_values=value)


# ---------------------------------------------------------------------------
# Distances / kNN
# ---------------------------------------------------------------------------


def pairwise_distance(q, x, metric: str = "l2", *, block: int = 128):
    """[M,D] × [N,D] → [M,N] float32; kernel-padded under the hood."""
    use, interp = _use_pallas()
    if not use:
        return ref.pairwise_distance(q, x, metric)
    m, n = q.shape[0], x.shape[0]
    qp = _pad_to(_pad_to(q, 1, 128), 0, block)
    xp = _pad_to(_pad_to(x, 1, 128), 0, block)
    out = _distance.pairwise_distance_pallas(
        qp, xp, metric=metric, block_m=block, block_n=block, interpret=interp
    )
    return out[:m, :n]


def pairwise_distance_u8(
    cq, cx, scale: float, zero_point: float, metric: str = "l2", *,
    block: int = 128,
):
    """[M,D] × [N,D] *uint8 codes* → [M,N] float32 distances.

    Both operands must carry codes from the same affine spec
    (``value ≈ zero_point + scale·code``); zero-code padding is applied
    under the hood (it cancels in L2 and contributes nothing to the IP
    code sums — the ``D·zp²`` affine term uses the true D).
    """
    use, interp = _use_pallas()
    if not use:
        return ref.pairwise_distance_u8(
            jnp.asarray(cq), jnp.asarray(cx), scale, zero_point, metric
        )
    m, n = cq.shape[0], cx.shape[0]
    d = cq.shape[1]
    qp = _pad_to(_pad_to(jnp.asarray(cq), 1, 128, 0), 0, block, 0)
    xp = _pad_to(_pad_to(jnp.asarray(cx), 1, 128, 0), 0, block, 0)
    out = _distance.pairwise_distance_u8_pallas(
        qp, xp,
        jnp.full((1, 1), scale, jnp.float32),
        jnp.full((1, 1), zero_point, jnp.float32),
        metric=metric, d_real=d, block_m=block, block_n=block,
        interpret=interp,
    )
    return out[:m, :n]


def rerank_exact(
    data: np.ndarray,  # [N, D] full-precision vectors
    cand_ids: np.ndarray,  # [Q, C] candidate ids into data (-1 = pad)
    queries: np.ndarray,  # [Q, D] f32
    k: int,
    metric: str = "l2",
) -> tuple[np.ndarray, np.ndarray, int]:
    """The shared f32 re-rank epilogue of the quantized distance stages.

    The beam traverses in the cheap dtype (uint8 codes / bf16) and hands
    its top ``C = rerank·k`` candidates here; this recomputes their
    distances *exactly* in f32 — touching only the candidates' rows — and
    returns the k best per query by ``(distance, id)``, the same tie-break
    as the split re-rank.  Exact output distances also make per-shard
    quantization specs comparable across a routed pool merge.

    Returns ``(ids [Q, k] int64 -1-padded, dists [Q, k] f32 inf-padded,
    n_scored)`` where ``n_scored`` is the number of real candidate
    distances computed (the caller's ``n_rerank_distance_computations``).

    Runs on the host in numpy on purpose: candidate sets are ragged and
    tiny (C ≤ width) next to the traversal, and the gather is the whole
    cost; a TPU-resident engine would fuse this into the final top-k
    kernel instead.
    """
    cand_ids = np.asarray(cand_ids, np.int64)
    qf = np.asarray(queries, np.float32)
    nq, c = cand_ids.shape
    valid = cand_ids >= 0
    rows = np.asarray(
        data[np.maximum(cand_ids, 0).reshape(-1)], np.float32
    ).reshape(nq, c, -1)
    if metric == "ip":
        d = -np.einsum("qcd,qd->qc", rows, qf)
    else:
        diff = rows - qf[:, None, :]
        d = np.einsum("qcd,qcd->qc", diff, diff)
    pad = np.iinfo(np.int64).max
    # duplicate ids can reach a merged-topology pool only as -1 padding, but
    # a candidate list may still repeat an id across quantized ties; keep
    # the (distance, id) order deterministic
    ids_key = np.where(valid, cand_ids, pad)
    d_key = np.where(valid, d, np.inf).astype(np.float32)
    order = np.lexsort((ids_key, d_key), axis=1)[:, :k]
    top_ids = np.take_along_axis(ids_key, order, axis=1)
    top_d = np.take_along_axis(d_key, order, axis=1)
    out_ids = np.full((nq, k), -1, np.int64)
    out_d = np.full((nq, k), np.inf, np.float32)
    out_ids[:, : order.shape[1]] = np.where(top_ids == pad, -1, top_ids)
    out_d[:, : order.shape[1]] = np.where(top_ids == pad, np.inf, top_d)
    return out_ids, out_d, int(valid.sum())


def knn(q, x, k: int, metric: str = "l2", *, block: int = 128):
    """Exact kNN (ascending): [M,D] × [N,D] → ([M,k] dists, [M,k] idx)."""
    use, interp = _use_pallas()
    if not use:
        return ref.knn(q, x, k, metric)
    m, n = q.shape[0], x.shape[0]
    qp = _pad_to(_pad_to(q, 1, 128), 0, block)
    xp = _pad_to(_pad_to(x, 1, 128), 0, block)
    d, i = _topk.knn_pallas(
        qp, xp, k, metric=metric, n_real=n, block_m=block, block_n=block,
        interpret=interp,
    )
    return d[:m], i[:m]
