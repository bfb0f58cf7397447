"""The program's spans in a profiler trace: how many of each name, and how
long.

A ``repro.telemetry.Tracer(sink="profiler")`` installed while the JAX
profiler runs puts every program span (``build.*``, ``cagra.*``,
``merge.*``, ``search.engine``, ``split.*``, ``beam.*``, ``serve.flush``)
on the profiler's host plane under its bare name.  :func:`reduce` turns the
host events :func:`bench.trace.load_events` returns into
``{name: (count, seconds)}`` inside the ``bench.window`` span (the whole
trace when there is none): ``count`` is the events of that name that
overlap the window, ``seconds`` the union of their intervals clipped to it,
so spans of one name that nest or run on two threads at once count their
overlap once.  The readers under ``bench/layer_metrics/`` turn those into
per-build and per-engine-call numbers.
"""

from __future__ import annotations

import collections

from bench.trace import WINDOW_SPAN, Events, _clip, _union

ENGINE_SPAN = "search.engine"


def reduce(ev: Events) -> dict:
    lo, hi = float("-inf"), float("inf")
    for name, s, d in ev.host:
        if name == WINDOW_SPAN:
            lo, hi = s, s + d
    by_name: dict = collections.defaultdict(list)
    for name, s, d in ev.host:
        if name != WINDOW_SPAN:
            by_name[name].append((s, s + d))
    out = {}
    for name, intervals in by_name.items():
        inside = _clip(intervals, lo, hi)
        if inside:
            out[name] = (len(inside),
                         sum(e - s for s, e in _union(inside)) * 1e-9)
    return out


def per_build_s(obs: dict, name: str) -> float | None:
    """Seconds of span ``name`` per build in the traced window."""
    spans, builds = obs.get("spans"), obs.get("builds")
    if not spans or not builds or name not in spans:
        return None
    return spans[name][1] / len(builds)


def per_engine_call_ms(obs: dict, name: str) -> float | None:
    """Milliseconds of span ``name`` per ``search.engine`` span (one per
    engine call) in the traced window."""
    spans = obs.get("spans")
    if not spans or name not in spans or ENGINE_SPAN not in spans:
        return None
    return 1e3 * spans[name][1] / spans[ENGINE_SPAN][0]
