"""``repro.serving`` — the asyncio micro-batching **ANN query** server.

It accumulates single-query ``submit()`` calls into engine-sized batches
and drains them through :func:`repro.search.search` (any topology, any
backend, routed ``nprobe`` included).

Public surface::

    async with AnnServer(index, data=data,
                         config=ServingConfig(backend="jax",
                                              max_wait_ms=2.0)) as srv:
        result = await srv.submit(query)     # QueryResult(ids, latency_s)
        print(srv.stats.snapshot())          # p50/p95/p99, occupancy, QPS

Pieces (importable for reuse/testing): :class:`MicroBatcher` +
:class:`RequestQueue` (flush-on-``max_batch``/``max_wait_ms`` semantics,
bounded admission), :class:`ServerStats` (latency percentiles, batch
occupancy histogram, distance-computations/query), and the
:class:`SLOPolicy` protocol (:class:`FixedWindow`, :class:`AdaptiveWindow`)
that retunes the batching window from observed queue depth.
"""

from repro.serving.policy import (AdaptiveWindow, FixedWindow,  # noqa: F401
                                  SLOPolicy)
from repro.serving.queue import (MicroBatcher, PendingRequest,  # noqa: F401
                                 RequestQueue, ServerOverloadedError)
from repro.serving.server import (AnnServer, QueryResult,  # noqa: F401
                                  ServingConfig, USE_DEFAULT)
from repro.serving.stats import ServerStats  # noqa: F401

__all__ = [
    "AnnServer",
    "ServingConfig",
    "QueryResult",
    "ServerStats",
    "MicroBatcher",
    "RequestQueue",
    "PendingRequest",
    "ServerOverloadedError",
    "SLOPolicy",
    "FixedWindow",
    "AdaptiveWindow",
    "USE_DEFAULT",
]
