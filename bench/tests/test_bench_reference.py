"""The plain reference against numpy on small cases."""

import numpy as np
import pytest

from bench import reference


def _numpy_knn(x, q, k):
    d = ((q[:, None, :].astype(np.float64) - x[None].astype(np.float64))
         ** 2).sum(-1)
    ids = np.argsort(d, axis=1, kind="stable")[:, :k]
    return np.take_along_axis(d, ids, axis=1), ids


def test_exact_knn_matches_numpy():
    rng = np.random.default_rng(0)
    x = rng.normal(size=(700, 24)).astype(np.float32)
    q = rng.normal(size=(300, 24)).astype(np.float32)  # two query blocks
    d, ids = reference.exact_knn(x, q, 7)
    want_d, want_ids = _numpy_knn(x, q, 7)
    assert ids.dtype == np.int64 and ids.shape == (300, 7)
    np.testing.assert_array_equal(ids, want_ids)
    np.testing.assert_allclose(d, want_d, rtol=1e-4, atol=1e-4)


def test_int4_control_uses_four_bit_codes():
    rng = np.random.default_rng(1)
    x = rng.integers(0, 256, size=(400, 16)).astype(np.uint8)
    q = rng.integers(0, 256, size=(20, 16)).astype(np.uint8)
    d, _ = reference.exact_knn(x, q, 5, precision="int4")
    want_d, _ = _numpy_knn(x >> 4, q >> 4, 5)
    np.testing.assert_allclose(d, want_d, rtol=1e-6)
    with pytest.raises(ValueError):
        reference.exact_knn(x.astype(np.float32), q, 5, precision="int4")


@pytest.mark.parametrize("precision,lo,hi", [("high", 1e-7, 1e-3),
                                             ("bfloat16", 1e-4, 1e-1)])
def test_lower_precisions_round_as_stated(precision, lo, hi):
    rng = np.random.default_rng(2)
    x = (3 + rng.normal(size=(500, 96))).astype(np.float32)
    q = (3 + rng.normal(size=(40, 96))).astype(np.float32)
    exact, _ = reference.exact_knn(x, q, 10)
    d, _ = reference.exact_knn(x, q, 10, precision=precision)
    err = np.abs(d - exact).max() / np.abs(exact).max()
    assert lo < err < hi


def test_sq_dist64_by_hand():
    x = np.array([[0, 0], [3, 4], [1, 1]], np.uint8)
    q = np.array([[0, 0], [1, 2]], np.float32)
    got = reference.dist64(x, q, np.array([[1, 2], [0, 1]]))
    np.testing.assert_array_equal(got, [[25.0, 2.0], [5.0, 8.0]])


def _numpy_ip(x, q, k):
    """The k largest inner products, as ``−q·x`` ascending."""
    d = -(q.astype(np.float64) @ x.astype(np.float64).T)
    ids = np.argsort(d, axis=1, kind="stable")[:, :k]
    return np.take_along_axis(d, ids, axis=1), ids


def _varying_norms(seed, n, nq, dim):
    """Vectors whose norms vary by a factor of about four, so that the
    largest inner products are not the nearest in L2."""
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, dim)) * rng.uniform(0.5, 2.0, size=(n, 1))
    return x.astype(np.float32), rng.normal(size=(nq, dim)).astype(np.float32)


def test_exact_knn_ip_matches_numpy():
    x, q = _varying_norms(3, 700, 300, 24)  # two query blocks
    d, ids = reference.exact_knn(x, q, 7, metric="ip")
    want_d, want_ids = _numpy_ip(x, q, 7)
    assert ids.dtype == np.int64 and ids.shape == (300, 7)
    np.testing.assert_array_equal(ids, want_ids)
    np.testing.assert_allclose(d, want_d, rtol=1e-4, atol=1e-4)
    assert (np.diff(d, axis=1) >= 0).all()  # nearest (largest q·x) first
    # on these vectors the two metrics disagree
    assert (reference.exact_knn(x, q, 7)[1] != ids).any()
    with pytest.raises(ValueError):
        reference.exact_knn(x, q, 7, metric="cosine")


@pytest.mark.parametrize("precision,lo,hi", [("high", 1e-7, 1e-3),
                                             ("bfloat16", 1e-4, 1e-1)])
def test_ip_lower_precisions_round_as_stated(precision, lo, hi):
    rng = np.random.default_rng(2)
    x = (3 + rng.normal(size=(500, 96))).astype(np.float32)
    q = (3 + rng.normal(size=(40, 96))).astype(np.float32)
    exact, _ = reference.exact_knn(x, q, 10, metric="ip")
    d, _ = reference.exact_knn(x, q, 10, precision=precision, metric="ip")
    err = np.abs(d - exact).max() / np.abs(exact).max()
    assert lo < err < hi


def test_dist64_ip_by_hand():
    x = np.array([[0, 0], [3, 4], [1, 1]], np.uint8)
    q = np.array([[0, 0], [1, -2]], np.float32)
    got = reference.dist64(x, q, np.array([[1, 2], [1, 2]]), "ip")
    np.testing.assert_array_equal(got, [[0.0, 0.0], [5.0, 1.0]])
    with pytest.raises(ValueError):
        reference.dist64(x, q, np.array([[1, 2], [1, 2]]), "cosine")


def test_nearest_other_ip_leaves_the_row_out_by_its_index():
    x, _ = _varying_norms(4, 300, 1, 8)
    got = reference.nearest_other(x, metric="ip")
    dots = x.astype(np.float64) @ x.astype(np.float64).T
    own_best = dots.argmax(axis=1) == np.arange(len(x))
    assert (~own_best).any()  # some rows are not their own nearest
    np.fill_diagonal(dots, -np.inf)
    np.testing.assert_array_equal(got, dots.argmax(axis=1))
