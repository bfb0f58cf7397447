"""Sampled Lloyd's k-means for shard centroids (paper §IV step 1).

Like DiskANN, centroids are trained on a sample (``IndexConfig.kmeans_sample``)
and the full dataset is then streamed block-by-block through the partitioner.
The assignment hot loop is the pairwise-distance kernel (``kernels.ops``).
It is L2 k-means; under an inner-product index the partitioner hands it the
MIPS-lifted vectors (``core.partition.mips_lift``), in which L2 is the
right distance.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops


@functools.partial(jax.jit, static_argnames=("k", "iters"))
def _lloyd(x: jax.Array, init: jax.Array, k: int, iters: int):
    n = x.shape[0]

    def step(_, carry):
        centroids, _ = carry
        d = ops.pairwise_distance(x, centroids, "l2")
        assign = jnp.argmin(d, axis=1)
        one_hot = jax.nn.one_hot(assign, k, dtype=x.dtype)  # [N, k]
        sums = one_hot.T @ x  # [k, D]
        counts = one_hot.sum(axis=0)[:, None]  # [k, 1]
        new_centroids = sums / jnp.maximum(counts, 1.0)
        # empty clusters: re-seed at the point farthest from its centroid
        far = jnp.argmax(jnp.min(d, axis=1))
        empty = counts[:, 0] < 0.5
        new_centroids = jnp.where(empty[:, None], x[far][None, :], new_centroids)
        return new_centroids, assign

    centroids, assign = jax.lax.fori_loop(
        0, iters, step, (init, jnp.zeros((n,), jnp.int32))
    )
    return centroids, assign


def train_centroids(
    data: np.ndarray, k: int, *, iters: int = 12, sample: int = 65536, seed: int = 0
) -> np.ndarray:
    """Train k centroids on a uniform sample of `data` ([N, D] float-like)."""
    rng = np.random.default_rng(seed)
    n = data.shape[0]
    if n > sample:
        idx = rng.choice(n, size=sample, replace=False)
        x = np.asarray(data[np.sort(idx)], dtype=np.float32)
    else:
        x = np.asarray(data, dtype=np.float32)
    if x.shape[0] < k:
        raise ValueError(f"need at least k={k} points, got {x.shape[0]}")
    init = x[rng.choice(x.shape[0], size=k, replace=False)]
    centroids, _ = _lloyd(jnp.asarray(x), jnp.asarray(init), k, iters)
    return np.asarray(centroids)
