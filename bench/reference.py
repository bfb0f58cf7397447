"""Plain exact k-nearest-neighbour reference, independent of ``repro``.

Brute force in ``jax.numpy``: every query against every base vector, with
the dot products at ``Precision.HIGHEST`` (a TPU otherwise rounds f32
operands to bfloat16), in blocks of queries so that one block's ``[B, N]``
distance tile fits.  ``metric`` is the configuration's: ``"l2"`` ranks by
squared L2, ``‖q‖² + ‖x‖² − 2·q·x``; ``"ip"`` by inner product, as the
distance ``−q·x`` (the program's convention: smaller is closer).  The
distances of returned ids that the comparison judges are recomputed on the
host in float64 (:func:`dist64`).

``precision`` selects the control's lower precisions (``bench/control.py``),
for either metric: ``"high"`` is the three-pass bfloat16 product (TPU
``Precision.HIGH``), written out so that it computes the same on any
backend; ``"bfloat16"`` rounds the vectors to bfloat16; ``"int4"`` keeps the
top four bits of each uint8 code.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

PRECISIONS = ("highest", "high", "bfloat16", "int4")
METRICS = ("l2", "ip")
BLOCK = 256


def _dot(a, b, precision=jax.lax.Precision.HIGHEST):
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                               precision=precision,
                               preferred_element_type=jnp.float32)


def _bf16(a):
    """``a`` rounded to bfloat16, kept in float32.  ``reduce_precision`` is
    an explicit op: XLA may skip a round trip through a bfloat16 cast
    (excess precision), but not this."""
    return jax.lax.reduce_precision(a, exponent_bits=8, mantissa_bits=7)


def _split(a):
    """``a ≈ hi + lo`` with both parts in bfloat16."""
    hi = _bf16(a)
    return hi.astype(jnp.bfloat16), _bf16(a - hi).astype(jnp.bfloat16)


@functools.partial(jax.jit, static_argnames=("k", "precision", "metric"))
def _block_knn(q, x, k: int, precision: str, metric: str):
    if precision == "bfloat16":
        q, x = _bf16(q), _bf16(x)
        dots = _dot(q.astype(jnp.bfloat16), x.astype(jnp.bfloat16))
    elif precision == "high":
        # three bfloat16 passes, spelled out so the CPU computes what a
        # TPU's Precision.HIGH does: hi·hi + hi·lo + lo·hi
        (qh, ql), (xh, xl) = _split(q), _split(x)
        dots = _dot(qh, xh) + _dot(qh, xl) + _dot(ql, xh)
    else:
        dots = _dot(q, x)
    if metric == "ip":
        d = -dots
    else:
        d = (jnp.sum(q * q, axis=1, keepdims=True)
             + jnp.sum(x * x, axis=1)[None, :] - 2.0 * dots)
    neg, idx = jax.lax.top_k(-d, k)
    return -neg, idx


def _check_metric(metric: str) -> None:
    if metric not in METRICS:
        raise ValueError(f"metric must be one of {METRICS}, got {metric!r}")


def exact_knn(data: np.ndarray, queries: np.ndarray, k: int, *,
              precision: str = "highest",
              metric: str = "l2") -> tuple[np.ndarray, np.ndarray]:
    """``(dists [Q, k] f32, ids [Q, k] int64)``, nearest first: squared L2,
    or ``−q·x`` under ``"ip"``."""
    if precision not in PRECISIONS:
        raise ValueError(f"precision must be one of {PRECISIONS}")
    _check_metric(metric)
    data = np.asarray(data)
    queries = np.asarray(queries)
    if precision == "int4":
        if data.dtype != np.uint8:
            raise ValueError("the int4 control needs uint8 vectors")
        data, queries = data >> 4, queries >> 4
    x = jnp.asarray(data.astype(np.float32))
    nq = len(queries)
    pad = (-nq) % BLOCK
    qs = np.concatenate([queries.astype(np.float32),
                         np.zeros((pad, queries.shape[1]), np.float32)])
    dists, ids = [], []
    for s in range(0, len(qs), BLOCK):
        d, i = _block_knn(jnp.asarray(qs[s:s + BLOCK]), x, k, precision,
                          metric)
        dists.append(np.asarray(d))
        ids.append(np.asarray(i))
    return (np.concatenate(dists)[:nq],
            np.concatenate(ids)[:nq].astype(np.int64))


def nearest_other(vectors: np.ndarray, *, precision: str = "highest",
                  metric: str = "l2") -> np.ndarray:
    """For each row of ``vectors``, the index of its nearest other row
    ``[n]`` (the exact 1-NN graph within the set).  The row itself is left
    out by its index, wherever it ranks: under ``"ip"`` a row is not always
    its own nearest."""
    _, ids = exact_knn(vectors, vectors, min(2, len(vectors)),
                       precision=precision, metric=metric)
    self_first = ids[:, 0] == np.arange(len(vectors))
    if ids.shape[1] == 1:
        return np.where(self_first, -1, ids[:, 0])
    return np.where(self_first, ids[:, 1], ids[:, 0])


def dist64(data: np.ndarray, queries: np.ndarray, ids: np.ndarray,
           metric: str = "l2", block: int = 4096) -> np.ndarray:
    """The distance in float64 of ``queries[i]`` to each ``data[ids[i, j]]``
    (``ids`` must be valid), ``[Q, k]``: squared L2, or ``−q·x`` under
    ``"ip"``."""
    _check_metric(metric)
    out = np.empty(ids.shape, np.float64)
    for s in range(0, len(ids), block):
        rows = np.asarray(data[ids[s:s + block]], np.float64)
        q = np.asarray(queries[s:s + block], np.float64)[:, None, :]
        if metric == "ip":
            out[s:s + block] = -(rows * q).sum(axis=-1)
        else:
            out[s:s + block] = ((rows - q) ** 2).sum(axis=-1)
    return out


def norms64(v: np.ndarray) -> np.ndarray:
    """The L2 norm of each row of ``v``, in float64."""
    v = np.asarray(v, np.float64)
    return np.sqrt((v * v).sum(axis=-1))
