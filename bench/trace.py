"""Reduction of a JAX profiler trace to device busy time, per-operation
device time and the host activity in device idle gaps.

The profiler writes ``<dir>/plugins/profile/<time>/<host>.xplane.pb``.
:func:`load_events` reads it with ``jax.profiler.ProfileData`` into plain
tuples; :func:`reduce_events` turns those into numbers.  Device planes are
``/device:TPU:<n>``; an operation's device interval is an event on the
plane's ``XLA Ops`` line, and a program's on its ``XLA Modules`` line.  The
benchmark marks the traced window with the host annotation ``bench.window``.
"""

from __future__ import annotations

import collections
import dataclasses
import glob
import gzip
import os
import re

import numpy as np

WINDOW_SPAN = "bench.window"
DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
NO_SPAN = "no host span"  # host work outside any traced call, e.g. numpy
# a program's line name ends in the fingerprint of its compiled shape
_PROGRAM_ID = re.compile(r"\(-?\d+\)$")


@dataclasses.dataclass
class Events:
    # device -> line -> [(name, start_ns, dur_ns)]
    device: dict
    host: list  # [(name, start_ns, dur_ns)] host spans with a duration


@dataclasses.dataclass
class Summary:
    n_devices: int
    busy_s: float  # mean over devices of the union of op intervals
    op_s: dict  # op name -> device seconds, summed over devices
    module_s: dict  # program name (all its shapes) -> device seconds
    idle_gaps: list  # [(host activity, seconds)], longest total first
    window_ns: tuple | None  # (start, end) of the bench.window span


def xplane_file(log_dir: str) -> str:
    found = glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if len(found) != 1:
        raise RuntimeError(f"expected one xplane.pb under {log_dir}, "
                           f"found {len(found)}")
    return found[0]


def load_events(path: str) -> Events:
    """Events of an ``.xplane.pb`` file (or its gzip, ``.xplane.pb.gz``)."""
    from jax.profiler import ProfileData

    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            pd = ProfileData.from_serialized_xspace(f.read())
    else:
        pd = ProfileData.from_file(path)
    device: dict = {}
    host: list = []
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            lines = device.setdefault(plane.name, {})
            for line in plane.lines:
                if line.name in (OPS_LINE, MODULES_LINE):
                    lines[line.name] = [
                        (e.name, float(e.start_ns), float(e.duration_ns))
                        for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.duration_ns > 0:
                        host.append((e.name, float(e.start_ns),
                                     float(e.duration_ns)))
    return Events(device=device, host=host)


def _union(intervals: list) -> list:
    out: list = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _clip(intervals: list, lo: float, hi: float) -> list:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def reduce_events(ev: Events, top: int = 10) -> Summary:
    window = None
    for name, s, d in ev.host:
        if name == WINDOW_SPAN:
            window = (s, s + d)
    lo, hi = window if window else (float("-inf"), float("inf"))
    op_s: collections.Counter = collections.Counter()
    module_s: collections.Counter = collections.Counter()
    busy = []
    merged_all: list = []
    for lines in ev.device.values():
        ops = lines.get(OPS_LINE, [])
        for name, s, d in ops:
            if s + d > lo and s < hi:
                op_s[name] += d * 1e-9
        for name, s, d in lines.get(MODULES_LINE, []):
            if s + d > lo and s < hi:
                module_s[_PROGRAM_ID.sub("", name)] += d * 1e-9
        u = _union(_clip([(s, s + d) for _, s, d in ops], lo, hi))
        busy.append(sum(e - s for s, e in u) * 1e-9)
        merged_all.extend(u)
    gaps = []
    if window:
        u = _union(merged_all)
        t = lo
        for s, e in u:
            if s > t:
                gaps.append((t, s))
            t = max(t, e)
        if hi > t:
            gaps.append((t, hi))
    named: collections.Counter = collections.Counter()
    spans = [(n, s, s + d) for n, s, d in ev.host if n != WINDOW_SPAN]
    names = [n for n, _, _ in spans]
    starts = np.array([s for _, s, _ in spans], np.float64)
    ends = np.array([e for _, _, e in spans], np.float64)
    lengths = ends - starts
    for gs, ge in gaps:
        # the innermost host span that covers at least half of the gap
        ov = np.minimum(ends, ge) - np.maximum(starts, gs)
        cand = np.flatnonzero(ov >= 0.5 * (ge - gs))
        best = (names[cand[np.argmin(lengths[cand])]] if len(cand)
                else NO_SPAN)
        named[best] += (ge - gs) * 1e-9
    n_dev = len(ev.device)
    return Summary(
        n_devices=n_dev,
        busy_s=sum(busy) / n_dev if n_dev else 0.0,
        op_s=dict(op_s),
        module_s=dict(module_s),
        idle_gaps=named.most_common(top),
        window_ns=window,
    )


def matching_seconds(times: dict, *patterns: str) -> float:
    """Device seconds of every name that contains each of ``patterns``."""
    return sum(v for k, v in times.items() if all(p in k for p in patterns))
