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
