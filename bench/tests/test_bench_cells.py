"""Every cell end to end at a tiny size on the CPU, through the harness's
own run, with the Pallas kernels in interpret mode."""

import json

import pytest

from bench import common, run
from bench.tests.tiny import CELLS, IP_CELL, case

SEED = 2**31 + 11  # past 32 signed bits, as the benchmark's seeds may be


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("cell", CELLS + (IP_CELL,))
def test_cell_prints_a_contract_line(cell, trace, interpret):
    cell, overrides = case(cell)
    r = run.run_cell(cell, SEED, 1.0, bool(trace), require_tpu=False,
                     persistent_cache=False, overrides=overrides)
    json.loads(json.dumps(r))
    assert list(r)[:5] == ["correct", "attempted", "failed", "metrics",
                           "device"]
    assert list(r)[-1] == "checks"
    assert r["correct"] is True, r["checks"]
    assert r["attempted"] > 0 and r["failed"] == 0
    assert r["device"]["platform"] == "cpu" and r["device"]["count"] >= 1
    assert "memory_peak_bytes" in r["device"]
    bench = common.benchmark()
    kind = "per_layer" if trace else "end_to_end"
    listed = {m["name"]: m for m in common.cell_metrics(bench, cell, kind)}
    for name, m in r["metrics"].items():
        assert m == {"value": m["value"], "unit": listed[name]["unit"]}
    if trace:
        # without a chip no number is read from a device trace
        host = {n for n, m in listed.items() if m["source"] != "device_trace"}
        assert set(r["metrics"]) == host
        assert "busy_s" not in r["device"]
    else:
        assert set(r["metrics"]) == set(listed)
        assert r["metrics"]["setup_s"]["value"] > 0
        assert r["metrics"]["recall_at_10"]["value"] >= 0.8
    for c in r["checks"].values():
        assert set(c) == {"value", "limit"} and c["value"] <= c["limit"]


def test_same_seed_same_inputs():
    from bench import data

    cfg = common.config("bigann-u8")
    cfg = dict(cfg, n=500)
    a, b = data.deployment(cfg), data.deployment(cfg)
    assert a.data.dtype.name == "uint8" and (a.data == b.data).all()
    q1, q2 = data.queries(a, 50, SEED), data.queries(a, 50, SEED)
    assert (q1 == q2).all()
    assert not (q1 == data.queries(a, 50, SEED + 1)).all()
