"""Serve single-query ANN traffic through the micro-batching front-end.

Build a ScaleGANN index, stand up :class:`repro.serving.AnnServer`, fire an
open-loop Poisson stream of single-query ``submit()`` calls at it, and
print the telemetry — the difference between this and calling
``repro.search.search`` per query is the entire point of ``repro.serving``
(the jax engine only earns its QPS at dense batches).

    PYTHONPATH=src python examples/serve_ann.py
"""

import asyncio
import time

import numpy as np

from repro.common.compile_cache import enable_compile_cache
from repro.configs.base import IndexConfig
from repro.core.builder import build_scalegann
from repro.data.synthetic import make_clustered, recall_at
from repro.serving import AnnServer, ServerStats, ServingConfig


async def poisson_clients(srv: AnnServer, queries: np.ndarray,
                          n_requests: int, rate_qps: float):
    """Open-loop arrivals: submit on schedule, whether or not the server
    is keeping up (that's what makes the p95 honest)."""
    rng = np.random.default_rng(0)
    t_next = time.monotonic()
    futs = []
    for j in range(n_requests):
        t_next += rng.exponential(1.0 / rate_qps)
        delay = t_next - time.monotonic()
        if delay > 0:
            await asyncio.sleep(delay)
        futs.append(srv.submit_nowait(queries[j % len(queries)],
                                      t_submit=t_next))
    return await asyncio.gather(*futs)


async def main():
    ds = make_clustered(5000, 64, n_queries=256, spread=1.0, seed=0)
    cfg = IndexConfig(n_clusters=8, degree=16, build_degree=32,
                      epsilon=1.2, block_size=1024)
    res = build_scalegann(ds.data, cfg, n_workers=4)

    # a 3 ms batching window: enough to fill jax-sized batches at this
    # rate, small next to a p95 a user would notice (500/s keeps this
    # index comfortably below saturation; push the rate up to watch the
    # queue take over batch formation)
    sc = ServingConfig(backend="jax", k=10, width=96, max_batch=64,
                       max_wait_ms=3.0, adaptive_window=True)
    async with AnnServer(res.index, data=ds.data, config=sc) as srv:
        # warm the jit's batch-shape buckets, then measure steady state
        await poisson_clients(srv, ds.queries, n_requests=300,
                              rate_qps=500.0)
        srv.stats = ServerStats()
        outs = await poisson_clients(srv, ds.queries, n_requests=1000,
                                     rate_qps=500.0)

    ids = np.stack([o.ids for o in outs[:len(ds.queries)]])
    snap = srv.stats.snapshot()
    print(f"recall@10      {recall_at(ids, ds.gt, 10):.3f}")
    print(f"achieved QPS   {snap['qps']:.0f}")
    print(f"latency ms     p50={snap['latency_ms']['p50']:.1f} "
          f"p95={snap['latency_ms']['p95']:.1f} "
          f"p99={snap['latency_ms']['p99']:.1f}")
    print(f"batch occupancy mean={snap['batch_occupancy']['mean']:.1f} "
          f"max={snap['batch_occupancy']['max']}")
    print(f"distance comps/query {snap['distance_computations_per_query']:.0f}")


if __name__ == "__main__":
    enable_compile_cache()
    asyncio.run(main())
