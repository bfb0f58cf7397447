"""The span reduction, on a trace recorded on the CPU, and the readers of
the per-layer metrics that read program spans and counters.

``data/spans.xplane.pb.gz`` holds, under a profiler-sink tracer: a span
``pre`` that opens before ``bench.window`` and closes inside it; inside the
window one routed uint8 search of two queries (``search.engine`` over
``split.route``, two ``split.beam`` each with ``beam.stage`` and
``beam.device``, ``split.pool``, ``split.rerank``) and two ``pair`` spans on
two threads, the second opened 10 ms into the first; after the window a
span ``post``."""

import pathlib

import pytest

from bench import common, spans, trace

DATA = pathlib.Path(__file__).parent / "data"


@pytest.fixture(scope="module")
def recorded():
    ev = trace.load_events(str(DATA / "spans.xplane.pb.gz"))
    return ev, spans.reduce(ev)


def _one(ev, name):
    (e,) = [(s, s + d) for n, s, d in ev.host if n == name]
    return e


def test_counts_the_program_spans_in_the_window(recorded):
    _, got = recorded
    assert got["search.engine"][0] == 1
    for name in ("split.route", "split.pool", "split.rerank"):
        assert got[name][0] == 1, name
    assert got["split.beam"][0] == got["beam.stage"][0] \
        == got["beam.device"][0] == 2
    assert "post" not in got and trace.WINDOW_SPAN not in got


def test_seconds_are_the_union_clipped_to_the_window(recorded):
    ev, got = recorded
    w0, _ = _one(ev, trace.WINDOW_SPAN)
    _, pre_end = _one(ev, "pre")
    # the part of `pre` inside the window
    assert got["pre"] == (1, pytest.approx((pre_end - w0) * 1e-9))
    pair = [(s, s + d) for n, s, d in ev.host if n == "pair"]
    lo, hi = min(s for s, _ in pair), max(e for _, e in pair)
    assert max(s for s, _ in pair) < min(e for _, e in pair)  # overlap
    # overlapping spans of one name count their overlap once
    assert got["pair"] == (2, pytest.approx((hi - lo) * 1e-9))
    s, e = _one(ev, "search.engine")
    assert got["search.engine"][1] == pytest.approx((e - s) * 1e-9)


def test_no_window_takes_the_whole_trace():
    ev = trace.Events(device={}, host=[("a", 0.0, 10.0), ("a", 5.0, 10.0),
                                       ("b", 30.0, 5.0)])
    assert spans.reduce(ev) == {"a": (2, pytest.approx(15e-9)),
                                "b": (1, pytest.approx(5e-9))}


SERVER = {"n_batches": 4, "beam_calls_per_batch": 11.5,
          "h2d_bytes_per_batch": 64_025_600.0}
SPANS = {"search.engine": (4, 0.5), "split.route": (4, 0.02),
         "beam.stage": (40, 0.1), "cagra.knn": (32, 3.0),
         "cagra.detour": (32, 5.0), "cagra.prune": (32, 7.0)}
BUILDS = [{"shards_s": 16.0}, {"shards_s": 16.0}]

# metric -> (hand-made observations, the value read from them)
READERS = {
    "build.knn_s": ({"spans": SPANS, "builds": BUILDS}, 1.5),
    "build.detour_s": ({"spans": SPANS, "builds": BUILDS}, 2.5),
    "build.prune_s": ({"spans": SPANS, "builds": BUILDS}, 3.5),
    "split.route_ms.routed": ({"spans": SPANS}, 5.0),
    "beam.stage_ms.routed": ({"spans": SPANS}, 25.0),
    "beam.stage_ms.merged": ({"spans": SPANS}, 25.0),
    "split.beams_per_call.routed": ({"server": SERVER}, 11.5),
    "beam.h2d_mb.routed": ({"server": SERVER}, 64.0256),
    "beam.h2d_mb.merged": ({"server": SERVER}, 64.0256),
}


@pytest.mark.parametrize("metric", sorted(READERS))
def test_reader(metric):
    obs, want = READERS[metric]
    read = common.layer_reader(metric).read
    assert read(obs) == pytest.approx(want)
    assert read({}) is None
    # a program without the spans or counters (the trace holds none of the
    # program's spans, or the server's snapshot lacks the counters)
    older = {"spans": {"search.engine": (4, 0.5)}, "builds": BUILDS,
             "server": {"n_batches": 4, "engine_time_ms_per_batch": 9.0}}
    assert read(older) is None
    # an untraced window, or one without engine calls
    assert read({"spans": None, "builds": BUILDS,
                 "server": dict(SERVER, n_batches=0)}) is None


@pytest.mark.parametrize("cell", ["deep-f32.build", "bigann-u8.routed.closed",
                                  "deep-f32.merged.closed"])
def test_untraced_result_keeps_its_keys(cell, interpret):
    """``--trace 0`` prints what it printed before the program had spans
    and counters: the same keys, the end-to-end metrics alone."""
    from bench import run
    from bench.tests.tiny import TINY

    r = run.run_cell(cell, 2**31 + 13, 1.0, False, require_tpu=False,
                     persistent_cache=False, overrides=TINY)
    assert list(r) == ["correct", "attempted", "failed", "metrics",
                       "device", "checks"]
    assert list(r["device"]) == ["platform", "kind", "count",
                                 "memory_peak_bytes"]
    e2e = {"deep-f32.build": "build_vec_per_s"}.get(cell, "serve_qps")
    assert set(r["metrics"]) == {e2e, "recall_at_10", "setup_s"}


def test_profiler_installs_a_profiler_tracer_while_it_runs(tmp_path):
    """A traced window puts the program's spans in the trace, and leaves
    the process's tracer as it found it."""
    from bench import run
    from repro.telemetry import NULL_TRACER, current_tracer, use_tracer

    prof = run._Profiler(str(tmp_path))
    with use_tracer(None):
        prof.start()
        inside = current_tracer()
        with inside.span("split.route"):
            pass
        prof.stop()
        assert current_tracer() is NULL_TRACER
    assert inside.enabled and inside._annotation is not None
    prof.reduce()
    assert prof.spans["split.route"][0] == 1
    assert trace.WINDOW_SPAN not in prof.spans


def test_untraced_run_installs_no_tracer(monkeypatch, interpret):
    import repro.telemetry as telemetry
    from bench import run
    from bench.tests.tiny import TINY

    def refuse(_tracer):
        raise AssertionError("an untraced run installed a tracer")

    monkeypatch.setattr(telemetry, "set_tracer", refuse)
    r = run.run_cell("deep-f32.merged.closed", 5, 0.5, False,
                     require_tpu=False, persistent_cache=False,
                     overrides=TINY)
    assert r["correct"] is True, r["checks"]
