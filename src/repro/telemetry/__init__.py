"""Unified telemetry: structured spans, a metrics registry, and trace
export — one subsystem observing build, fleet, and serving.

The repo's headline claims are operational (build makespan under
preemption, served QPS at recall parity); this package is how a run
*shows its work*:

* :mod:`repro.telemetry.trace` — hierarchical span tracer with
  Chrome/Perfetto trace-event export, thread-safe, fake-clock
  deterministic.  A whole fleet build and a whole serving session render
  on one timeline.  Its ``"profiler"`` sink puts the same spans into a
  JAX profiler trace instead, beside the device's events.
* :mod:`repro.telemetry.metrics` — counters / gauges / reservoir
  histograms with Prometheus text exposition and a JSON snapshot;
  ``ServerStats``, the fleet executor and the build drivers feed it.
* :mod:`repro.telemetry.jit` — compile-event listeners and the
  engine-call :class:`SignatureGuard` (the mid-traffic-retrace bug class
  as a metric, not a rediscovery).
* :mod:`repro.telemetry.validate` — trace schema + semantic checks the
  traced smoke benches are CI-guarded with.

Telemetry defaults to the no-op recorder (:data:`NULL_TRACER`): hot
paths gate on ``tracer.enabled`` and pay one branch when disabled.
Install a tracer process-wide with :func:`use_tracer` (every bench's
``--trace-out`` does), or hand one to the component that owns the run
(``AnnServer(tracer=...)``, ``build_scalegann_fleet(tracer=...)``).
"""

from repro.telemetry.jit import SignatureGuard, install_compile_listener
from repro.telemetry.metrics import (DEFAULT_BUCKETS, Counter, Gauge,
                                     Histogram, MetricsRegistry,
                                     current_registry, parse_prometheus,
                                     set_registry, use_registry)
from repro.telemetry.trace import (NULL_SPAN, NULL_TRACER, ManualClock,
                                   NullTracer, Span, Tracer, current_tracer,
                                   set_tracer, use_tracer)
from repro.telemetry.validate import (check_durability_trace,
                                      check_fleet_trace,
                                      check_serving_trace,
                                      validate_chrome_trace)

__all__ = [
    "Counter", "DEFAULT_BUCKETS", "Gauge", "Histogram", "ManualClock",
    "MetricsRegistry", "NULL_SPAN", "NULL_TRACER", "NullTracer",
    "SignatureGuard", "Span", "Tracer", "check_durability_trace",
    "check_fleet_trace", "check_serving_trace", "current_registry",
    "current_tracer", "install_compile_listener", "parse_prometheus",
    "set_registry", "set_tracer", "use_registry", "use_tracer",
    "validate_chrome_trace",
]
