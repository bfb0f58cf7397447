"""The control: the plain reference in the program's place, one precision
below what the configuration states, comes out not correct."""

import pytest

from bench import control


@pytest.mark.parametrize("cell", ["deep-f32.build", "bigann-u8.routed.closed",
                                  "deep-f32.merged.closed"])
def test_control_is_not_correct(cell):
    r = control.readings(cell, 4242, overrides={"n": 20000})
    assert r["precision"] == control.CONTROL[
        "uint8" if cell.startswith("bigann") else "float32"]
    assert r["correct"] is False, r["checks"]
    assert r["checks"]["order_gap"]["value"] > r["checks"]["order_gap"][
        "limit"]


def test_reference_at_the_stated_precision_is_correct():
    r = control.readings("deep-f32.merged.closed", 4242, "highest",
                         overrides={"n": 20000})
    assert r["correct"] is True, r["checks"]


def _ip_overrides():
    from bench import common
    from bench.tests.tiny import OOD

    # readings() overrides whole top-level keys: the index block entire
    index = dict(common.config("deep-f32")["index"], metric="ip")
    return {"n": 20000, "metric": "ip", "index": index, "queries": OOD}


def test_ip_reference_at_the_stated_precision_is_correct():
    r = control.readings("deep-f32.merged.closed", 4242, "highest",
                         overrides=_ip_overrides())
    assert r["correct"] is True, r["checks"]
    assert r["recall_at_10"] == 1.0


def test_ip_bfloat16_control_is_not_correct():
    r = control.readings("deep-f32.merged.closed", 4242, "bfloat16",
                         overrides=_ip_overrides())
    assert r["correct"] is False, r["checks"]
    assert r["checks"]["order_gap"]["value"] > r["checks"]["order_gap"][
        "limit"]


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_ip_three_pass_control_reads_where_ties_are_near(seed):
    """Under "ip" the three-pass products err by about 1e-6 of ‖q‖·‖x‖,
    which inverts only near ties; on vectors with a common offset, whose
    inner products crowd, order_gap reads it while HIGHEST reads none."""
    import numpy as np

    from bench import check, reference

    rng = np.random.default_rng(seed)
    x = (3 + rng.normal(size=(20000, 96))).astype(np.float32)
    q = (3 + rng.normal(size=(500, 96))).astype(np.float32)
    limits = {"order_gap_max": 1e-7, "recall_at_10_min": 0.9}
    _, gt = reference.exact_knn(x, q, 10, metric="ip")
    read = {}
    for prec in ("highest", "high"):
        _, ids = reference.exact_knn(x, q, 10, precision=prec, metric="ip")
        checks, _ = check.compare(x, q, np.arange(len(q)), ids, gt, 0,
                                  limits, 10, "ip")
        read[prec] = (check.passed(checks), checks["order_gap"]["value"])
    assert read["highest"] == (True, 0.0)
    assert read["high"][0] is False, read
