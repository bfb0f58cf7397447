"""The trace reduction, on hand-made events and on a trace recorded on a
TPU v5e (``data/probe.xplane.pb.gz``: one ``knn_pallas`` call and one
batched beam search inside a ``bench.window`` span)."""

import pathlib

import pytest

from bench import trace

DATA = pathlib.Path(__file__).parent / "data"


def test_busy_is_the_union_clipped_to_the_window():
    ev = trace.Events(
        device={"/device:TPU:0": {
            trace.OPS_LINE: [("a", 0.0, 10.0), ("b", 5.0, 10.0),
                             ("a", 30.0, 10.0), ("c", 95.0, 20.0)],
            trace.MODULES_LINE: [("jit_f", 0.0, 40.0)],
        }},
        host=[(trace.WINDOW_SPAN, 2.0, 98.0), ("dispatch", 16.0, 10.0),
              ("outer", 10.0, 90.0)],
    )
    s = trace.reduce_events(ev)
    assert s.n_devices == 1 and s.window_ns == (2.0, 100.0)
    # [2, 15) + [30, 40) + [95, 100)
    assert s.busy_s == pytest.approx(28e-9)
    assert s.op_s == pytest.approx({"a": 20e-9, "b": 10e-9, "c": 20e-9})
    # gaps [15, 30) (dispatch overlaps 10) and [40, 95) (outer only)
    assert dict(s.idle_gaps) == pytest.approx({"dispatch": 15e-9,
                                               "outer": 55e-9})
    assert trace.matching_seconds(s.op_s, "a") == pytest.approx(20e-9)


def test_recorded_tpu_trace():
    ev = trace.load_events(str(DATA / "probe.xplane.pb.gz"))
    s = trace.reduce_events(ev)
    assert s.n_devices == 1 and s.window_ns is not None
    window_s = (s.window_ns[1] - s.window_ns[0]) * 1e-9
    assert 0 < s.busy_s < window_s
    assert trace.matching_seconds(s.op_s, "knn_pallas",
                                  "tpu_custom_call") > 0
    assert any("batch_beam" in name for name in s.module_s)
    assert s.idle_gaps and all(sec > 0 for _, sec in s.idle_gaps)
