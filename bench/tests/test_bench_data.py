"""The query pools: the default pool of each configuration is the one it
always was, and the ``"ood"`` source moves queries off the base."""

import hashlib

import numpy as np
import pytest

from bench import common, data, reference

SEED = 2**31 + 11

# sha256 of the pool's bytes at SEED, taken before the ``queries`` source
# existed
POOLS = {
    "deep-f32": "d8dd7f1b49161cc333c38d789f6bffd7925250394330fb571c16be6de412f828",
    "bigann-u8": "c5a73507975657e790a7cf28a1a26f0a41ae2b50448915f965300ea81a9251cc",
}


@pytest.mark.parametrize("name", sorted(POOLS))
def test_default_pool_is_unchanged(name):
    cfg = common.config(name)
    assert "queries" not in cfg
    dep = data.deployment(cfg)
    pool = data.queries(dep, cfg["assumed"]["query_pool"], SEED,
                        cfg.get("queries"))
    assert hashlib.sha256(pool.tobytes()).hexdigest() == POOLS[name]


def _ood(shift):
    return {"source": "ood", "n_centres": 24, "shift": shift,
            "spread": 0.35, "centre_seed": 1}


def _nn_dist(x, q):
    d, _ = reference.exact_knn(x, q, 1)
    return np.sqrt(np.maximum(d[:, 0], 0.0))


@pytest.mark.parametrize("name", sorted(POOLS))
def test_ood_queries_leave_the_base_as_shift_grows(name):
    """(median distance from a query to its nearest base vector) / (median
    distance from a base vector to its nearest other one), against the
    in-distribution pool's."""
    cfg = dict(common.config(name), n=5000)
    dep = data.deployment(cfg)
    x = dep.data
    base = np.median(np.sqrt(np.maximum(
        reference.exact_knn(x, x, 2)[0][:, 1], 0.0)))

    def ratio(source):
        q = data.queries(dep, 500, SEED, source)
        assert q.dtype == x.dtype and q.shape == (500, x.shape[1])
        return np.median(_nn_dist(x, q)) / base

    inside = ratio(None)
    by_shift = [ratio(_ood(s)) / inside for s in (0.0, 0.25, 0.5, 1.0)]
    assert abs(by_shift[0] - 1) < 0.1
    assert all(a < b for a, b in zip(by_shift, by_shift[1:]))
    assert by_shift[-1] > 2


def test_ood_centres_are_fixed_by_the_configuration():
    cfg = dict(common.config("deep-f32"), n=500)
    dep = data.deployment(cfg)
    a = data.queries(dep, 50, 1, _ood(0.5))
    assert (a == data.queries(dep, 50, 1, _ood(0.5))).all()
    assert not (a == data.queries(dep, 50, 2, _ood(0.5))).all()
    c = data.query_centres(dep, _ood(0.5))
    assert c.shape == (24, dep.centers.shape[1])
    assert not np.allclose(c, data.query_centres(
        dep, dict(_ood(0.5), centre_seed=2)))
    with pytest.raises(ValueError):
        data.queries(dep, 50, 1, {"source": "image"})
