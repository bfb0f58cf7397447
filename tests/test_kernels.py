"""Pallas kernels (interpret mode) vs pure-jnp oracles: shape/dtype sweeps."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from numpy.testing import assert_allclose

from repro.kernels import ops, ref
from repro.kernels.distance import pairwise_distance_pallas
from repro.kernels.topk import bitonic_sort_pairs, knn_pallas


def _rand(rng, shape, dtype):
    x = rng.normal(size=shape).astype(np.float32)
    return jnp.asarray(x, dtype)


# ---------------------------------------------------------------------------
# pairwise distance
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("m,n,d", [(128, 128, 128), (256, 128, 256),
                                   (128, 384, 512)])
@pytest.mark.parametrize("metric", ["l2", "ip"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_pairwise_distance_kernel(rng, m, n, d, metric, dtype):
    q = _rand(rng, (m, d), dtype)
    x = _rand(rng, (n, d), dtype)
    got = pairwise_distance_pallas(q, x, metric=metric, interpret=True)
    want = ref.pairwise_distance(q, x, metric)
    tol = 1e-4 if dtype == jnp.float32 else 0.15
    assert_allclose(np.asarray(got), np.asarray(want), rtol=tol, atol=tol)


def test_pairwise_distance_padding_path(rng):
    """ops wrapper pads ragged shapes; values must be exact."""
    ops.set_pallas_mode("force_interpret")
    try:
        q = _rand(rng, (37, 33), jnp.float32)
        x = _rand(rng, (101, 33), jnp.float32)
        got = ops.pairwise_distance(q, x, "l2")
        want = ref.pairwise_l2(q, x)
        assert got.shape == (37, 101)
        assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4,
                        atol=1e-4)
    finally:
        ops.set_pallas_mode("auto")


# ---------------------------------------------------------------------------
# fused kNN
# ---------------------------------------------------------------------------


def test_bitonic_sort_matches_numpy(rng):
    v = jnp.asarray(rng.normal(size=(4, 64)).astype(np.float32))
    i = jnp.asarray(rng.integers(0, 1000, (4, 64)).astype(np.int32))
    sv, si = bitonic_sort_pairs(v, i)
    order = np.argsort(np.asarray(v), axis=1, kind="stable")
    assert_allclose(np.asarray(sv), np.take_along_axis(np.asarray(v), order,
                                                       axis=1), rtol=1e-6)


# (m, n, n_real, D, k, data): the two generic shapes, then the benchmark
# configurations' widths — deep-f32 (D = 96, f32), bigann-u8 (D = 128,
# uint8 codes) and text2image-ip (D = 200, f32) — at the build's k = L + 1
# = 129 and around it, over a ragged n_real the padding rows must not reach
_KNN_CASES = [
    pytest.param(128, 256, 256, 128, 8, "f32", id="m128-n256-d128-k8"),
    pytest.param(128, 128, 128, 256, 32, "f32", id="m128-n128-d256-k32"),
] + [
    pytest.param(128, 384, 300, d, k, data, id=f"{shape}-D{d}-k{k}")
    for shape, d, data in (("deep-f32", 96, "f32"),
                           ("bigann-u8", 128, "u8"),
                           ("text2image-ip", 200, "f32"))
    for k in (10, 64, 128, 129)
]


@pytest.mark.parametrize("m,n,n_real,d,k,data", _KNN_CASES)
@pytest.mark.parametrize("metric", ["l2", "ip"])
def test_knn_kernel(rng, m, n, n_real, d, k, data, metric):
    def draw(rows):
        if data == "u8":
            return rng.integers(0, 256, (rows, d)).astype(np.float32)
        return rng.normal(size=(rows, d)).astype(np.float32)

    lanes = -(-d // 128) * 128  # zero lanes change neither metric
    q = np.zeros((m, lanes), np.float32)
    x = np.zeros((n, lanes), np.float32)
    q[:, :d] = draw(m)
    x[:n_real, :d] = draw(n_real)
    # padding rows that would win under either metric if not masked
    decoys = 4.0 * np.resize(q[:, :d], (n - n_real, d))
    x[n_real:, :d] = decoys if metric == "ip" else q[: n - n_real, :d]
    q, x = jnp.asarray(q), jnp.asarray(x)
    dist, idx = knn_pallas(q, x, k, metric=metric, n_real=n_real,
                           interpret=True)
    want_d, _ = ref.knn(q, x[:n_real], k, metric)
    assert_allclose(np.asarray(dist), np.asarray(want_d), rtol=1e-3,
                    atol=1e-3)
    idx = np.asarray(idx)
    assert idx.min() >= 0 and idx.max() < n_real
    # indices may differ on ties; check distance agreement instead
    got_rows = np.asarray(ref.pairwise_distance(q, x[:n_real], metric))[
        np.arange(m)[:, None], idx
    ]
    assert_allclose(got_rows, np.asarray(want_d), rtol=1e-3, atol=1e-3)


def test_knn_kernel_masks_padding(rng):
    q = _rand(rng, (128, 128), jnp.float32)
    x = _rand(rng, (256, 128), jnp.float32)
    d, i = knn_pallas(q, x, 4, n_real=100, interpret=True)
    assert int(np.asarray(i).max()) < 100
