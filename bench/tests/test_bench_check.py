"""The comparison that decides ``correct``, on hand-made answers."""

import numpy as np
import pytest

from bench import check

LIMITS = {"order_gap_max": 1e-6, "recall_at_10_min": 0.8}


def _case():
    rng = np.random.default_rng(0)
    x = rng.normal(size=(200, 8)).astype(np.float32)
    q = rng.normal(size=(6, 8)).astype(np.float32)
    d = ((q[:, None] - x[None]) ** 2).sum(-1)
    gt = np.argsort(d, axis=1)[:, :4]
    return x, q, gt


def test_exact_answers_pass():
    x, q, gt = _case()
    checks, recall = check.compare(x, q, np.arange(6), gt, gt, 0, LIMITS, 4)
    assert recall == 1.0 and check.passed(checks)
    assert checks["order_gap"]["value"] == 0.0


def test_answers_out_of_order_fail():
    x, q, gt = _case()
    checks, recall = check.compare(x, q, np.arange(6), gt[:, ::-1], gt, 0,
                                   LIMITS, 4)
    assert recall == 1.0
    assert checks["order_gap"]["value"] > 1e-3 and not check.passed(checks)


def test_invalid_and_unanswered_count():
    x, q, gt = _case()
    bad = gt.copy()
    bad[0, 1] = bad[0, 0]  # repeated id
    bad[1, 2] = -1  # padding
    bad[2, 3] = len(x)  # out of range
    checks, recall = check.compare(x, q, np.arange(6), bad, gt, 3, LIMITS, 4)
    assert checks["invalid"]["value"] == 3
    assert checks["unanswered"]["value"] == 3
    assert recall == 0.5 and not check.passed(checks)


def test_ties_count_as_hits():
    x = np.array([[0, 0], [1, 0], [0, 1], [5, 5]], np.uint8)
    q = np.zeros((1, 2), np.float32)
    gt = np.array([[0, 1]])  # id 2 ties id 1
    checks, recall = check.compare(x, q, [0], np.array([[0, 2]]), gt, 0,
                                   LIMITS, 2)
    assert recall == 1.0 and check.passed(checks)
    checks, recall = check.compare(x, q, [0], np.array([[0, 3]]), gt, 0,
                                   LIMITS, 2)
    assert recall == 0.5 and not check.passed(checks)


def _shards():
    rng = np.random.default_rng(1)
    x = rng.normal(size=(300, 8)).astype(np.float32)
    ids = [np.arange(0, 200), np.arange(100, 300)]  # overlapping, as replicas
    out = []
    for s in ids:
        d = ((x[s][:, None] - x[s][None]) ** 2).sum(-1)
        np.fill_diagonal(d, np.inf)
        order = np.argsort(d, axis=1)
        out.append((s, s[order[:, 0]], s[order[:, 1]]))
    return x, out


def test_nn_gap_exact_first_neighbours_pass():
    x, shards = _shards()
    assert check.nn_gap(x, [(s, first) for s, first, _ in shards]) == 0.0


def test_nn_gap_second_nearest_fails():
    x, shards = _shards()
    s, first, second = shards[1]
    first = first.copy()
    first[7] = second[7]
    graphs = [(shards[0][0], shards[0][1]), (s, first)]
    assert check.nn_gap(x, graphs) > 1e-3


@pytest.mark.parametrize("bad", [-1, "self", "other_shard"])
def test_nn_gap_entry_not_in_shard_is_infinite(bad):
    x, shards = _shards()
    s, first, _ = shards[0]
    first = first.copy()
    first[3] = {-1: -1, "self": s[3], "other_shard": 250}[bad]
    assert check.nn_gap(x, [(s, first)]) == float("inf")


def _ip_case():
    """Base vectors whose norms vary by a factor of about four, so that the
    largest inner products are not the nearest in L2."""
    rng = np.random.default_rng(0)
    x = (rng.normal(size=(200, 8))
         * rng.uniform(0.5, 2.0, size=(200, 1))).astype(np.float32)
    q = rng.normal(size=(6, 8)).astype(np.float32)
    gt = np.argsort(-(q @ x.T), axis=1)[:, :4]
    l2 = np.argsort(((q[:, None] - x[None]) ** 2).sum(-1), axis=1)[:, :4]
    return x, q, gt, l2


def test_ip_exact_answers_pass():
    x, q, gt, _ = _ip_case()
    checks, recall = check.compare(x, q, np.arange(6), gt, gt, 0, LIMITS, 4,
                                   "ip")
    assert recall == 1.0 and check.passed(checks)
    assert checks["order_gap"]["value"] == 0.0


def test_ip_answers_out_of_order_fail():
    x, q, gt, _ = _ip_case()
    checks, recall = check.compare(x, q, np.arange(6), gt[:, ::-1], gt, 0,
                                   LIMITS, 4, "ip")
    assert recall == 1.0
    assert checks["order_gap"]["value"] > 1e-3 and not check.passed(checks)


def test_ip_order_gap_by_hand():
    x = np.array([[1, 0], [3, 0], [0, 1]], np.float32)
    q = np.array([[2, 0]], np.float32)  # −q·x: −2, −6, 0
    gt = np.array([[1, 0]])
    checks, recall = check.compare(x, q, [0], np.array([[0, 1]]), gt, 0,
                                   LIMITS, 2, "ip")
    # the increase −2 − (−6) over ‖q‖·‖x_(k)‖ = 2·1, x_(k) being id 0
    assert recall == 1.0 and checks["order_gap"]["value"] == 2.0


def test_ip_ties_count_as_hits():
    x = np.array([[2, 0], [1, 0], [1, 5], [0, 1]], np.uint8)
    q = np.array([[1, 0]], np.float32)
    gt = np.array([[0, 1]])  # id 2 ties id 1: q·x = 1 for both
    checks, recall = check.compare(x, q, [0], np.array([[0, 2]]), gt, 0,
                                   LIMITS, 2, "ip")
    assert recall == 1.0 and check.passed(checks)
    checks, recall = check.compare(x, q, [0], np.array([[0, 3]]), gt, 0,
                                   LIMITS, 2, "ip")
    assert recall == 0.5 and not check.passed(checks)


def test_exact_l2_answers_are_not_correct_under_ip():
    x, q, gt, l2 = _ip_case()
    assert check.passed(check.compare(x, q, np.arange(6), l2, l2, 0, LIMITS,
                                      4)[0])
    checks, recall = check.compare(x, q, np.arange(6), l2, gt, 0, LIMITS, 4,
                                   "ip")
    assert recall < LIMITS["recall_at_10_min"] and not check.passed(checks)


def _ip_shards():
    rng = np.random.default_rng(1)
    x = (rng.normal(size=(300, 8))
         * rng.uniform(0.5, 2.0, size=(300, 1))).astype(np.float32)
    out = []
    for s in (np.arange(0, 200), np.arange(100, 300)):
        d = -(x[s] @ x[s].T)
        np.fill_diagonal(d, np.inf)
        order = np.argsort(d, axis=1)
        out.append((s, s[order[:, 0]], s[order[:, 1]]))
    return x, out


def test_ip_nn_gap_exact_first_neighbours_pass():
    x, shards = _ip_shards()
    graphs = [(s, first) for s, first, _ in shards]
    assert check.nn_gap(x, graphs, "ip") == 0.0
    assert check.nn_gap(x, graphs) > 1e-3  # not the nearest in L2


def test_ip_nn_gap_second_nearest_fails():
    x, shards = _ip_shards()
    s, first, second = shards[1]
    first = first.copy()
    first[7] = second[7]
    graphs = [(shards[0][0], shards[0][1]), (s, first)]
    assert check.nn_gap(x, graphs, "ip") > 1e-3
