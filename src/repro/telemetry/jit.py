"""Profiling hooks around the jitted engine: compile-event counters and
the engine-call signature guard.

The repo's worst historical serving bug class (found in PR 3, guarded by
shape bucketing + startup pretrace ever since) is the **mid-traffic jit
retrace**: a new engine-call shape arriving after warm-up pays a
multi-second trace inside some unlucky request's latency.  This module
turns that class from a rediscovery into two first-class metrics:

* :func:`install_compile_listener` taps ``jax.monitoring`` — every
  compile/trace/lower duration event JAX emits increments
  ``jit_compile_events_total{event=...}``, lands in the
  ``jit_compile_seconds`` histogram, and (when a tracer is installed)
  draws a span on the ``jit`` track, so compilations are *visible on the
  same timeline* as the requests they delay.
* :class:`SignatureGuard` tracks distinct engine-call signatures —
  ``(backend, batch shape, nprobe, dtype)`` in the serving worker — and
  flags any signature first seen *after* warm-up: exactly the situation
  where a retrace can land mid-traffic.  The serving layer feeds
  ``serving_post_warm_signatures_total`` from it.

The listener imports ``jax.monitoring`` only when it is installed, so a
numpy-only path that never starts a server or bench never loads JAX here.
"""

from __future__ import annotations

import threading
from typing import Hashable

from repro.telemetry.metrics import current_registry
from repro.telemetry.trace import current_tracer

__all__ = ["SignatureGuard", "install_compile_listener"]

_install_lock = threading.Lock()
_installed = False

# duration-event substrings that mean "the compiler ran"
_COMPILE_MARKERS = ("compile", "trace", "lower")


def _on_duration(event: str, duration_s: float, **_kw) -> None:
    low = event.lower()
    if not any(m in low for m in _COMPILE_MARKERS):
        return
    short = event.strip("/").rsplit("/", 1)[-1]
    reg = current_registry()
    reg.counter(
        "jit_compile_events_total",
        "jax compile/trace/lower duration events, by event name",
        event=short,
    ).inc()
    reg.histogram(
        "jit_compile_seconds", "duration of jax compile events",
        buckets=(0.001, 0.01, 0.05, 0.1, 0.5, 1.0, 5.0, 15.0, 60.0),
    ).observe(duration_s)
    tr = current_tracer()
    if tr.enabled:
        t1 = tr.now()
        tr.complete("jit.compile", t1 - duration_s, t1, track="jit",
                    event=short)


def install_compile_listener() -> None:
    """Register the ``jax.monitoring`` duration listener (idempotent —
    safe to call from every server/bench startup).  Events are forwarded
    to whatever registry/tracer is *current at event time*, so a bench
    that installs its own tracer after this still captures compiles."""
    global _installed
    with _install_lock:
        if not _installed:
            from jax import monitoring
            monitoring.register_event_duration_secs_listener(_on_duration)
            _installed = True


class SignatureGuard:
    """First-seen detector for engine-call signatures.

    ``warm(sig)`` records signatures covered by startup pretrace;
    ``observe(sig)`` returns ``(is_new, after_warmup)`` — a ``(True,
    True)`` result is the mid-traffic-retrace risk the serving metrics
    count.  Thread-safe; signatures must be hashable.
    """

    def __init__(self):
        self._lock = threading.Lock()
        self._seen: set[Hashable] = set()
        self._warmed = False

    def warm(self, sig: Hashable) -> None:
        with self._lock:
            self._seen.add(sig)

    def finish_warmup(self) -> None:
        with self._lock:
            self._warmed = True

    @property
    def n_signatures(self) -> int:
        return len(self._seen)

    def observe(self, sig: Hashable) -> tuple[bool, bool]:
        with self._lock:
            if sig in self._seen:
                return False, False
            self._seen.add(sig)
            return True, self._warmed
