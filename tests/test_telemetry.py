"""The telemetry subsystem's contracts: byte-stable span trees under a
fake clock, a truly free no-op recorder on the serving hot path, the
Prometheus exposition round-trip, the program's spans in a JAX profiler
trace beside its device work, and the two traced end-to-end flows
(serving request decomposition, fleet preemption lifecycle) the smoke
benches are CI-guarded with."""

import asyncio
import gc
import glob
import json
import os
import sys
import time

import jax
import numpy as np
import pytest

from repro.configs.base import IndexConfig
from repro.core import merge
from repro.core.builder import build_scalegann
from repro.core.cagra import ShardIndex
from repro.core.merge import merge_shard_indexes
from repro.core.scheduler import RuntimeModel
from repro.data.synthetic import make_clustered
from repro.fleet import PreemptionInjector, build_scalegann_fleet
from repro.search import ShardTopology, jax_backend, search
from repro.serving import AnnServer, ServerStats, ServingConfig
from repro.telemetry import (NULL_SPAN, NULL_TRACER, ManualClock,
                             MetricsRegistry, SignatureGuard, Tracer,
                             check_fleet_trace, check_serving_trace,
                             current_tracer, parse_prometheus, use_tracer,
                             validate_chrome_trace)

# ---------------------------------------------------------------------------
# span tracer: determinism, nesting, export schema
# ---------------------------------------------------------------------------


def _record_fixture_run(tracer: Tracer, clock: ManualClock) -> None:
    """One deterministic recording: nested spans on explicit and inherited
    tracks, an instant, a post-hoc complete, and an async lane."""
    with tracer.span("build.partition", track="build", n=1000):
        clock.advance(0.5)
    with tracer.span("fleet.shard_build", track="worker-0", shard=3):
        clock.advance(0.1)
        tracer.instant("fleet.preempt.notice", shard=3)  # inherits track
        with tracer.span("vamana.pass"):  # inherits worker-0
            t0 = tracer.now()
            clock.advance(0.2)
            tracer.complete("vamana.round", t0, tracer.now(), round=1)
        clock.advance(0.05)
    t0 = tracer.now()
    clock.advance(0.003)
    t1 = tracer.now()
    tracer.async_complete("serve.request", "req0", t0, t1,
                          cat="serving", track="requests")
    tracer.async_complete("serve.engine", "req0", t0, t1,
                          cat="serving", track="requests")


def test_span_tree_byte_stable_under_manual_clock():
    runs = []
    for _ in range(2):
        clock = ManualClock()
        tr = Tracer(clock, process="fixture")
        _record_fixture_run(tr, clock)
        runs.append(tr.to_json())
    assert runs[0] == runs[1]  # identical bytes, not just equal objects
    obj = json.loads(runs[0])
    assert validate_chrome_trace(obj) == []

    events = [e for e in obj["traceEvents"] if e["ph"] != "M"]
    by_name = {e["name"]: e for e in events}
    # nesting: the round span's parent is the pass span, whose parent is
    # the attempt span — and track inheritance put them all on worker-0
    tracks = {e["tid"]: e["args"]["name"]
              for e in obj["traceEvents"]
              if e["ph"] == "M" and e["name"] == "thread_name"}
    attempt = by_name["fleet.shard_build"]
    nested = by_name["vamana.pass"]
    rnd = by_name["vamana.round"]
    assert tracks[attempt["tid"]] == "worker-0"
    assert nested["tid"] == attempt["tid"] == rnd["tid"]
    assert nested["args"]["parent_id"] == attempt["args"]["span_id"]
    assert rnd["args"]["parent_id"] == nested["args"]["span_id"]
    assert by_name["fleet.preempt.notice"]["tid"] == attempt["tid"]
    # durations are µs of fake-clock time
    assert by_name["vamana.round"]["dur"] == pytest.approx(0.2e6)
    assert by_name["build.partition"]["dur"] == pytest.approx(0.5e6)


def test_span_error_annotated_not_swallowed():
    clock = ManualClock()
    tr = Tracer(clock)
    with pytest.raises(ValueError):
        with tr.span("build.shard", track="build"):
            raise ValueError("boom")
    (ev,) = [e for e in tr.to_chrome()["traceEvents"] if e["ph"] == "X"]
    assert ev["args"]["error"] == "ValueError"


def test_tracer_event_cap_drops_never_blocks():
    clock = ManualClock()
    tr = Tracer(clock, max_events=3)
    for i in range(6):
        t0 = tr.now()
        clock.advance(0.001)
        tr.complete("x", t0, tr.now())
    obj = tr.to_chrome()
    assert len([e for e in obj["traceEvents"] if e["ph"] == "X"]) == 3
    assert obj["otherData"]["dropped"] == 3


def test_use_tracer_installs_and_restores():
    assert current_tracer() is NULL_TRACER
    tr = Tracer(ManualClock())
    with use_tracer(tr):
        assert current_tracer() is tr
    assert current_tracer() is NULL_TRACER


# ---------------------------------------------------------------------------
# the disabled recorder is free
# ---------------------------------------------------------------------------


def test_null_tracer_shares_singletons():
    s1 = NULL_TRACER.span("serve.request", track="x", a=1)
    s2 = NULL_TRACER.span()
    assert s1 is s2  # no per-call allocation even when called
    assert s1.set(a=2) is s1
    with s1:
        pass
    assert NULL_TRACER.enabled is False
    assert NULL_TRACER.to_chrome()["traceEvents"] == []


def _serving_hot_pattern(tr, n: int) -> None:
    """The exact gating idioms the program uses: one branch, and the
    kwargs-building call is never reached when disabled; the span sites
    (``serve.flush``, ``split.*``, ``beam.*``, ``cagra.*``, ``merge.*``,
    ``build.gather``) take the shared no-op span instead of calling
    ``span``."""
    for _ in range(n):
        if tr.enabled:
            tr.instant("serve.retrace_risk", track="jit",
                       backend="jax", batch=8)
        with tr.span("serve.flush") if tr.enabled else NULL_SPAN:
            with tr.span("split.beam") if tr.enabled else NULL_SPAN:
                with tr.span("beam.stage") if tr.enabled else NULL_SPAN:
                    pass


def test_null_tracer_hot_path_zero_allocations():
    _serving_hot_pattern(NULL_TRACER, 10)  # warm code objects / freelists
    gc.collect()
    before = sys.getallocatedblocks()
    _serving_hot_pattern(NULL_TRACER, 10_000)
    after = sys.getallocatedblocks()
    # transient loop internals are freed before we read again; the gated
    # telemetry itself must leave nothing live
    assert after - before <= 2


def test_null_tracer_creates_no_trace_annotation(monkeypatch, tiny_routed):
    """The program's span sites never reach the profiler while the no-op
    recorder is installed; a profiler-sink tracer reaches it once per
    span."""
    made = []

    class Counting(jax.profiler.TraceAnnotation):
        def __init__(self, name, **kw):
            made.append(name)
            super().__init__(name, **kw)

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Counting)
    q = tiny_routed.data[:8]
    assert current_tracer() is NULL_TRACER
    search(tiny_routed, q, 5, backend="jax", width=32, nprobe="auto",
           dtype="uint8")
    assert made == []
    with use_tracer(Tracer(sink="profiler")):
        search(tiny_routed, q, 5, backend="jax", width=32, nprobe="auto",
               dtype="uint8")
    assert made[0] == "search.engine" and "split.route" in made


# ---------------------------------------------------------------------------
# the profiler sink: the program's spans on the device trace's clock
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def tiny_build():
    ds = make_clustered(800, 16, n_queries=16, seed=3)
    cfg = IndexConfig(n_clusters=4, degree=8, build_degree=16,
                      block_size=512)
    return ds, cfg, build_scalegann(ds.data, cfg, algo="cagra")


@pytest.fixture(scope="module")
def tiny_routed(tiny_build):
    ds, _, res = tiny_build
    return res.shard_topology(ds.data)


def _profiled(log_dir, fn):
    """Run ``fn()`` under a JAX profiler trace with a profiler-sink tracer
    installed; returns its result and the trace's host events as
    ``(name, start_ns, end_ns, line)``."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    with use_tracer(Tracer(sink="profiler")):
        jax.profiler.start_trace(str(log_dir), profiler_options=opts)
        try:
            out = fn()
        finally:
            jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(str(log_dir), "plugins", "profile",
                                     "*", "*.xplane.pb"))
    events = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for i, line in enumerate(plane.lines):
                events += [(e.name, e.start_ns, e.start_ns + e.duration_ns,
                            (plane.name, i)) for e in line.events]
    return out, events


def _named(events, name):
    return [e for e in events if e[0] == name]


def _inside(inner, outer) -> bool:
    """Nested on one thread's line, on the profiler's one clock."""
    return (inner[3] == outer[3] and outer[1] <= inner[1]
            and inner[2] <= outer[2])


def _children(events, outer, name):
    return [e for e in _named(events, name) if _inside(e, outer)]


def _assert_beam_calls(events, outer, n):
    """``n`` beams inside ``outer``, each staged then run on the device,
    the device span holding the host event of its ``_batch_beam``
    execution."""
    stages = _children(events, outer, "beam.stage")
    devices = _children(events, outer, "beam.device")
    assert len(stages) == len(devices) == n
    for st, dev in zip(sorted(stages, key=lambda e: e[1]),
                       sorted(devices, key=lambda e: e[1])):
        assert st[2] <= dev[1]
        assert _children(events, dev, "PjitFunction(_batch_beam)")


def test_profiler_sink_routed_search_spans(tmp_path, tiny_routed):
    q = tiny_routed.data[:12]
    (_, stats), events = _profiled(tmp_path, lambda: search(
        tiny_routed, q, 5, backend="jax", width=32, nprobe="auto",
        dtype="uint8"))
    (engine,) = _named(events, "search.engine")  # the bare name: no args
    for name in ("split.route", "split.pool", "split.rerank"):
        assert len(_children(events, engine, name)) == 1, name
    beams = _children(events, engine, "split.beam")
    assert len(beams) == stats.n_beam_calls >= 1
    for b in beams:
        _assert_beam_calls(events, b, 1)
    _assert_beam_calls(events, engine, stats.n_beam_calls)
    order = [_named(events, n)[0][1] for n in
             ("split.route", "split.beam", "split.pool", "split.rerank")]
    assert order == sorted(order)


def test_profiler_sink_merged_search_spans(tmp_path, tiny_build):
    ds, _, res = tiny_build
    (_, stats), events = _profiled(tmp_path, lambda: search(
        res.index, ds.queries, 5, backend="jax", data=ds.data, width=32))
    (engine,) = _named(events, "search.engine")
    assert stats.n_beam_calls == 1
    _assert_beam_calls(events, engine, 1)
    assert not _named(events, "split.beam")


def test_profiler_sink_build_spans(tmp_path, tiny_build):
    ds, cfg, _ = tiny_build
    res, events = _profiled(tmp_path, lambda: build_scalegann(
        ds.data, cfg, algo="cagra"))
    n = len(res.shards)
    assert len(_named(events, "build.partition")) == 1
    (shards,) = _named(events, "build.shards")
    assert len(_children(events, shards, "build.gather")) == n
    built = _children(events, shards, "build.shard")
    assert len(built) == n
    for b in built:
        for name in ("cagra.knn", "cagra.detour", "cagra.prune"):
            assert len(_children(events, b, name)) == 1, name
    (merge,) = _named(events, "build.merge")
    for name in ("merge.edges", "merge.sort", "merge.distances",
                 "merge.cap"):
        assert len(_children(events, merge, name)) == 1, name


def test_profiler_sink_records_nothing_in_memory():
    tr = Tracer(sink="profiler")
    with tr.span("a", track="x", n=1) as sp:
        assert sp.set(n=2) is sp
    tr.instant("b", n=1)
    tr.complete("c", 0.0, 1.0)
    tr.async_complete("d", "id", 0.0, 1.0)
    assert tr.to_chrome()["traceEvents"][1:] == []
    with pytest.raises(ValueError):
        Tracer(sink="file")


# ---------------------------------------------------------------------------
# engine-call counters: beams dispatched and bytes uploaded
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("nprobe,dtype", [(None, "f32"), (1, "f32"),
                                          ("auto", "uint8")])
def test_split_counters_match_probed_shards_and_uploads(
        monkeypatch, tiny_routed, nprobe, dtype):
    uploaded = []
    beam = jax_backend._batch_beam

    def spy(x, g, e, q, *rest):
        uploaded.append(x.nbytes + g.nbytes + e.nbytes + q.nbytes)
        return beam(x, g, e, q, *rest)

    monkeypatch.setattr(jax_backend, "_batch_beam", spy)
    tracer = Tracer(ManualClock())
    q = tiny_routed.data[::50][:12]
    with use_tracer(tracer):
        _, stats = search(tiny_routed, q, 5, backend="jax", width=32,
                          nprobe=nprobe, dtype=dtype)
    probed = [e for e in tracer.to_chrome()["traceEvents"]
              if e["name"] == "split.beam"]
    assert stats.n_beam_calls == len(probed) == len(uploaded)
    if nprobe is None:
        assert stats.n_beam_calls == len(tiny_routed.shard_ids)
    assert stats.h2d_bytes == sum(uploaded)


def test_merged_h2d_bytes_are_the_uploaded_shapes(tiny_build):
    ds, _, res = tiny_build
    n, d = ds.data.shape
    _, stats = search(res.index, ds.queries, 5, backend="jax", data=ds.data,
                      width=32, n_entries=16)
    entries = len(res.index.entry_points(16))
    assert stats.n_beam_calls == 1
    assert stats.h2d_bytes == (n * d * 4 + res.index.graph.size * 4
                               + entries * 4 + len(ds.queries) * d * 4)


def test_merge_distances_span_records_its_path(tiny_build):
    ds, cfg, res = tiny_build
    n, d = ds.data.shape
    edges = set()
    for s, g in zip(res.shards, res.shard_graphs):
        for row, gid in enumerate(s.ids):
            edges |= {(int(gid), int(s.ids[j])) for j in g[row]
                      if j >= 0 and s.ids[j] != gid}
    idxs = [ShardIndex(graph=g, n_distance_computations=0)
            for g in res.shard_graphs]
    tracer = Tracer(ManualClock())
    with use_tracer(tracer):
        merge_shard_indexes(res.shards, idxs, n, cfg.degree, data=ds.data)
    (span,) = [e for e in tracer.to_chrome()["traceEvents"]
               if e["name"] == "merge.distances"]
    blocks = -(-len(edges) // merge._EDGE_BLOCK)
    # the CPU backend reports no memory limit: the device path runs
    args = {k: span["args"][k] for k in ("resident", "n_edges", "h2d_bytes")}
    assert args == {
        "resident": True, "n_edges": len(edges),
        "h2d_bytes": n * d * 4 + 2 * blocks * merge._EDGE_BLOCK * 4}


# ---------------------------------------------------------------------------
# metrics registry + Prometheus round-trip
# ---------------------------------------------------------------------------


def test_prometheus_round_trip_through_parser():
    reg = MetricsRegistry()
    reg.counter("requests_total", "served requests",
                outcome="completed").inc(7)
    reg.counter("requests_total", outcome="shed").inc()
    reg.gauge("queue_depth", "pending requests").set(3.5)
    h = reg.histogram("latency_seconds", "e2e latency",
                      buckets=(0.01, 0.1, 1.0))
    for v in (0.005, 0.05, 0.05, 0.5, 5.0):
        h.observe(v)
    # a label value that needs escaping must survive the trip
    reg.counter("errs_total", kind='bad "quote"\\path\n').inc(2)

    text = reg.to_prometheus()
    parsed = parse_prometheus(text)
    assert parsed[("requests_total",
                   frozenset({("outcome", "completed")}))] == 7
    assert parsed[("requests_total", frozenset({("outcome", "shed")}))] == 1
    assert parsed[("queue_depth", frozenset())] == 3.5
    assert parsed[("latency_seconds_count", frozenset())] == 5
    assert parsed[("latency_seconds_sum", frozenset())] == pytest.approx(
        5.605)
    # cumulative le buckets, +Inf last
    assert parsed[("latency_seconds_bucket",
                   frozenset({("le", "0.01")}))] == 1
    assert parsed[("latency_seconds_bucket",
                   frozenset({("le", "0.1")}))] == 3
    assert parsed[("latency_seconds_bucket", frozenset({("le", "1")}))] == 4
    assert parsed[("latency_seconds_bucket",
                   frozenset({("le", "+Inf")}))] == 5
    assert parsed[("errs_total",
                   frozenset({("kind", 'bad "quote"\\path\n')}))] == 2


def test_registry_modeling_errors_raise():
    reg = MetricsRegistry()
    reg.counter("a_total", x="1")
    with pytest.raises(ValueError):  # same name, different label set
        reg.counter("a_total", y="1")
    with pytest.raises(ValueError):  # same name, different kind
        reg.gauge("a_total", x="1")
    with pytest.raises(ValueError):
        reg.counter("bad name")
    with pytest.raises(ValueError):
        reg.counter("a_total", x="1").inc(-1)


def test_histogram_reservoir_deterministic_quantiles():
    mk = lambda: MetricsRegistry().histogram(  # noqa: E731
        "h", buckets=(1.0,), reservoir=64)
    a, b = mk(), mk()
    for i in range(1000):
        v = float((i * 37) % 101)
        a.observe(v)
        b.observe(v)
    assert a.percentile(50) == b.percentile(50)  # seeded reservoir
    assert a.summary() == b.summary()
    assert a.count == 1000 and a.summary(2.0)["mean"] == pytest.approx(
        2.0 * a.total / a.count)


def test_snapshot_shape():
    reg = MetricsRegistry()
    reg.counter("c_total", "help here", k="v").inc(3)
    reg.histogram("h_seconds").observe(0.2)
    snap = reg.snapshot()
    assert snap["c_total"]["type"] == "counter"
    assert snap["c_total"]["series"] == [
        {"labels": {"k": "v"}, "value": 3.0}]
    hs = snap["h_seconds"]["series"][0]
    assert hs["count"] == 1 and hs["sum"] == pytest.approx(0.2)
    assert hs["summary"]["p50"] == pytest.approx(0.2)


# ---------------------------------------------------------------------------
# signature guard (the mid-traffic-retrace metric)
# ---------------------------------------------------------------------------


def test_signature_guard_flags_only_post_warmup_novelty():
    g = SignatureGuard()
    g.warm(("jax", 8, 1, "f32"))
    assert g.observe(("jax", 8, 1, "f32")) == (False, False)  # pretraced
    assert g.observe(("jax", 16, 1, "f32")) == (True, False)  # pre-warm new
    g.finish_warmup()
    assert g.observe(("jax", 32, 1, "f32")) == (True, True)  # the bad case
    assert g.observe(("jax", 32, 1, "f32")) == (False, False)  # now known
    assert g.n_signatures == 3


# ---------------------------------------------------------------------------
# trace validation negatives
# ---------------------------------------------------------------------------


def test_validate_chrome_trace_catches_malformed_events():
    assert validate_chrome_trace([]) != []  # not an object
    assert validate_chrome_trace({}) != []  # no traceEvents
    bad = {"traceEvents": [
        {"name": "x", "ph": "Z", "pid": 1, "tid": 1, "ts": 0},
        {"name": "x", "ph": "X", "pid": 1, "tid": 1, "ts": 0, "dur": -1},
        {"name": "x", "ph": "e", "pid": 1, "tid": 1, "ts": 0, "id": "a",
         "cat": "c"},
    ]}
    errs = validate_chrome_trace(bad)
    assert any("invalid ph" in e for e in errs)
    assert any("dur" in e for e in errs)
    assert any("no open 'b'" in e for e in errs)
    unbalanced = {"traceEvents": [
        {"name": "x", "ph": "b", "pid": 1, "tid": 1, "ts": 0, "id": "a",
         "cat": "c"},
    ]}
    assert any("unbalanced" in e for e in validate_chrome_trace(unbalanced))


# ---------------------------------------------------------------------------
# ServerStats feeds the registry
# ---------------------------------------------------------------------------


def test_server_stats_queue_engine_split_and_exposition():
    st = ServerStats()
    for ms in (10.0, 20.0, 30.0):
        st.record_completion(0.0, ms / 1e3, queue_wait_s=0.4 * ms / 1e3,
                             engine_s=0.5 * ms / 1e3)
    snap = st.snapshot()
    assert snap["queue_wait_ms"]["p50"] == pytest.approx(8.0)
    assert snap["engine_service_ms"]["p50"] == pytest.approx(10.0)
    assert snap["latency_ms"]["p50"] == pytest.approx(20.0)
    text = st.to_prometheus()
    parsed = parse_prometheus(text)
    assert parsed[("serving_requests_total",
                   frozenset({("outcome", "completed")}))] == 3
    assert parsed[("serving_queue_wait_seconds_count", frozenset())] == 3
    assert parsed[("serving_engine_service_seconds_sum",
                   frozenset())] == pytest.approx(0.03)


# ---------------------------------------------------------------------------
# traced end-to-end: serving request decomposition
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def ring():
    rng = np.random.default_rng(0)
    data = rng.normal(size=(40, 8)).astype(np.float32)
    g = np.stack([(np.arange(40) + s) % 40 for s in range(1, 6)],
                 axis=1).astype(np.int32)
    topo = ShardTopology(data=data,
                         shard_ids=[np.arange(40, dtype=np.int64)],
                         shard_graphs=[g])
    return data, topo


def test_traced_serving_decomposes_request_latency(ring):
    data, topo = ring
    tracer = Tracer(clock=time.monotonic)  # must match the server clock

    async def main():
        sc = ServingConfig(backend="numpy", k=5, width=64, max_batch=4,
                           max_wait_ms=2.0)
        async with AnnServer(topo, config=sc, tracer=tracer) as srv:
            futs = [srv.submit_nowait(data[i]) for i in range(12)]
            for f in futs:
                await f

    asyncio.run(main())
    obj = tracer.to_chrome()
    assert validate_chrome_trace(obj) == []
    chk = check_serving_trace(obj)
    assert chk["ok"], chk
    assert chk["n_requests"] == 12
    assert chk["min_coverage_seen"] >= 0.95


def test_traced_serving_counts_post_warm_signatures(ring):
    """With pretrace disabled, the first engine-call signature is by
    definition first seen after warm-up — the guard must count it."""
    data, topo = ring
    tracer = Tracer(clock=time.monotonic)

    async def main():
        sc = ServingConfig(backend="numpy", k=5, width=64, max_batch=4,
                           max_wait_ms=2.0, pretrace=False)
        async with AnnServer(topo, config=sc, tracer=tracer) as srv:
            await srv.submit_nowait(data[0])
            snap = srv.stats.registry.snapshot()
        series = snap["serving_post_warm_signatures_total"]["series"]
        assert series[0]["value"] >= 1

    asyncio.run(main())
    names = {e["name"] for e in tracer.to_chrome()["traceEvents"]}
    assert "serve.retrace_risk" in names


def test_server_sees_a_tracer_installed_after_start(ring):
    """Without ``tracer=`` the server looks the process-wide tracer up at
    every flush: one installed mid-run gets each flush as a ``serve.flush``
    span on the event loop's thread, holding the engine's spans of the
    executor thread in time; an explicit ``tracer=`` stays pinned."""
    data, topo = ring
    late = Tracer(clock=time.monotonic)
    pinned = Tracer(clock=time.monotonic)

    async def main():
        sc = ServingConfig(backend="numpy", k=5, width=64, max_batch=4,
                           max_wait_ms=2.0)
        async with AnnServer(topo, config=sc) as srv, \
                AnnServer(topo, config=sc, tracer=pinned) as fixed:
            # both warmed and serving before the tracer is installed
            await srv.submit(data[0])
            await fixed.submit(data[0])
            with use_tracer(late):
                await srv.submit(data[1])
                await fixed.submit(data[2])
            return srv.stats.snapshot()

    snap = asyncio.run(main())
    ev = [e for e in late.to_chrome()["traceEvents"] if e["ph"] == "X"]
    (f,) = [e for e in ev if e["name"] == "serve.flush"]
    # the search.engine span of each server's call (both follow the
    # process-wide tracer); the late-traced server's lies inside its flush
    engines = [e for e in ev if e["name"] == "search.engine"]
    assert len(engines) == 2
    assert any(f["tid"] != g["tid"]  # loop thread vs executor thread
               and f["ts"] <= g["ts"]
               and g["ts"] + g["dur"] <= f["ts"] + f["dur"]
               for g in engines)
    pinned_names = {e["name"] for e in pinned.to_chrome()["traceEvents"]}
    assert "serve.flush" in pinned_names and "serve.request" in pinned_names
    assert snap["n_batches"] == 2
    assert snap["beam_calls_per_batch"] == 1.0  # one shard, numpy beam
    assert snap["h2d_bytes_per_batch"] == 0.0  # the numpy beam uploads none


def test_server_stats_expose_beam_calls_and_uploads():
    from repro.search import SearchStats

    st = ServerStats()
    assert st.snapshot()["beam_calls_per_batch"] == 0.0
    st.observe_batch(3, 4, SearchStats(n_beam_calls=5, h2d_bytes=1000),
                     0.01)
    st.observe_batch(4, 4, SearchStats(n_beam_calls=3, h2d_bytes=3000),
                     0.01)
    snap = st.snapshot()
    # padding scales neither: the calls and the bytes really happen
    assert snap["beam_calls_per_batch"] == 4.0
    assert snap["h2d_bytes_per_batch"] == 2000.0
    parsed = parse_prometheus(st.to_prometheus())
    assert parsed[("serving_beam_calls_total", frozenset())] == 8
    assert parsed[("serving_h2d_bytes_total", frozenset())] == 4000


# ---------------------------------------------------------------------------
# traced end-to-end: fleet preemption lifecycle
# ---------------------------------------------------------------------------


def test_traced_fleet_shows_preemption_lifecycle():
    ds = make_clustered(600, 16, n_queries=8, seed=1)
    cfg = IndexConfig(n_clusters=4, degree=8, build_degree=16,
                      block_size=512)
    tracer = Tracer()
    out = build_scalegann_fleet(
        ds.data, cfg, n_workers=2, backend="numpy",
        # kill at round 2: round 1's checkpoint exists, so the retry
        # resumes instead of restarting (a round-1 kill has no checkpoint)
        injector=PreemptionInjector(kill_shard_at={0: 2}),
        runtime_model=RuntimeModel(seconds_per_vector=1e-4),
        tracer=tracer,
    )
    obj = tracer.to_chrome()
    assert validate_chrome_trace(obj) == []
    chk = check_fleet_trace(obj)
    assert chk["ok"], chk
    assert chk["n_kills"] >= 1 and chk["n_resumes"] >= 1

    r = out.report
    assert r.metrics["fleet_preemptions_total"]["series"][0]["value"] == 1
    assert r.metrics["fleet_rounds_total"]["series"][0]["value"] == \
        r.rounds_completed
    # the per-shard timeline carries the lifecycle in order
    tl = {t.shard: t for t in r.shard_timelines}
    killed = tl[0]
    assert killed.attempts == 2
    kinds = [e[1] for e in killed.events]
    assert "kill" in kinds and "preempted" in kinds and "resume" in kinds
    assert kinds.index("kill") < kinds.index("resume")
    times = [e[0] for e in killed.events]
    assert times == sorted(times)
    for t in tl.values():
        assert all(e[3] == t.shard for e in t.events)
