"""Serving benchmark: open-loop Poisson arrivals against ``AnnServer``.

The question BENCH_serving.json answers: how much of the engine's
batch-throughput win (BENCH_search.json: jax ≈ 4× numpy QPS at batch 256)
does the micro-batching front-end recover for *single-query* traffic, and
what does the ``max_wait_ms`` latency budget buy?

Method — open loop, the honest way to measure a server: arrivals follow a
Poisson process at a fixed offered rate, submitted on schedule whether or
not the server is keeping up, and each request's latency is charged from
its
*scheduled* arrival.  Each (backend × offered-rate × window) trial reports
achieved QPS, p50/p95/p99 end-to-end latency, batch-occupancy histogram,
and distance computations per query, next to the batch-1 blocking baseline
(call ``repro.search.search`` per query, the no-serving-layer strawman).

    PYTHONPATH=src python benchmarks/bench_serving.py           # full sweep
    PYTHONPATH=src python benchmarks/bench_serving.py --smoke   # CI-sized

Acceptance (ISSUE 3): on the 2k fixture the micro-batched server must
sustain >= 2x the batch-1 blocking QPS at the same recall (jax backend).

Acceptance (ISSUE 6): the fused ``pallas`` engine must beat the jax
backend on end-to-end served QPS at recall@10 within 0.01
(``claim.pallas_fused_ge_jax_qps_at_recall_within_001``).  Both backends
sweep the same rate×window grid plus a shared saturation trial (offered
load pinned to 4× the measured jax batch-1 rate, so the comparison is
capacity vs capacity, not offered-rate cap vs offered-rate cap).  The
``--smoke`` profile additionally drains one tiny trial through the
force-interpret Pallas kernel — the CI-testable fallback of the fused
engine — recording its recall next to its (interpreter-priced, not
claim-bearing) throughput.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import pathlib
import time

import numpy as np

from repro.common.compile_cache import enable_compile_cache
from repro.configs.base import IndexConfig
from repro.core import builder
from repro.data.synthetic import make_clustered
from repro.search import search
from repro.serving import (AnnServer, ServerOverloadedError, ServerStats,
                           ServingConfig)
from repro.telemetry import (NULL_TRACER, Tracer, check_serving_trace,
                             set_tracer, validate_chrome_trace)

K = 10
WIDTH = 64
DIM = 32
N_VECTORS = 2000
OUT_PATH = pathlib.Path(__file__).resolve().parent.parent / \
    "BENCH_serving.json"


def _recall(pairs: list[tuple[int, np.ndarray]], gt: np.ndarray) -> float:
    """Mean recall@K over ``(query_index, result_ids)`` pairs (explicit
    indices, so rejected requests can't shift the alignment)."""
    hits = 0
    for j, ids in pairs:
        hits += len(set(ids.tolist()) & set(gt[j % len(gt), :K].tolist()))
    return hits / (K * max(len(pairs), 1))


def bench_batch1_blocking(topo, ds, backend: str, n: int) -> dict:
    """The no-serving-layer baseline: one blocking search() per query."""
    search(topo, ds.queries[:1], K, backend=backend, width=WIDTH)  # warm
    pairs = []
    t0 = time.perf_counter()
    for j in range(n):
        ids, _ = search(topo, ds.queries[j % len(ds.queries)][None, :], K,
                        backend=backend, width=WIDTH)
        pairs.append((j, ids[0]))
    wall = time.perf_counter() - t0
    return {
        "qps": n / wall,
        "mean_latency_ms": wall / n * 1e3,
        "recall_at_10": _recall(pairs, ds.gt),
    }


async def _submit_poisson(srv: AnnServer, ds, n: int, rate_qps: float,
                          seed: int) -> tuple[list, int]:
    """Open-loop arrival generator: requests are stamped with their
    *scheduled* arrival time, so scheduling slip (the generator falling
    behind) is charged to latency exactly like a queued network arrival."""
    rng = np.random.default_rng(seed)
    gaps = rng.exponential(1.0 / rate_qps, size=n)
    futs, n_rejected = [], 0
    t_next = time.monotonic()
    for j in range(n):
        t_next += gaps[j]
        delay = t_next - time.monotonic()
        if delay > 0:
            await asyncio.sleep(delay)
        try:
            fut = srv.submit_nowait(
                ds.queries[j % len(ds.queries)], t_submit=t_next)
        except ServerOverloadedError:  # bounded queue under overload
            n_rejected += 1
            continue
        futs.append((j, fut))  # keep the query index: rejections must
        # not shift the result↔ground-truth alignment
    outs = await asyncio.gather(*(f for _, f in futs))
    return [(j, o) for (j, _), o in zip(futs, outs)], n_rejected


async def run_trial(topo, ds, *, backend: str, rate_qps: float,
                    max_wait_ms: float, n_requests: int, max_batch: int,
                    warmup: int, adaptive: bool = False) -> dict:
    cfg = ServingConfig(backend=backend, k=K, width=WIDTH,
                        max_batch=max_batch, max_wait_ms=max_wait_ms,
                        max_pending=8192, adaptive_window=adaptive)
    async with AnnServer(topo, config=cfg) as srv:
        if warmup:
            await _submit_poisson(srv, ds, warmup, rate_qps, seed=1)
            srv.stats = ServerStats()  # measure steady state only
        outs, n_rejected = await _submit_poisson(
            srv, ds, n_requests, rate_qps, seed=2)
    snap = srv.stats.snapshot()
    lat = snap["latency_ms"]
    pcts = ("p50", "p95", "p99", "mean")
    return {
        "offered_qps": rate_qps,
        "max_wait_ms": max_wait_ms,
        "adaptive_window": adaptive,
        "qps": snap["qps"],
        "recall_at_10": _recall([(j, o.ids) for j, o in outs], ds.gt),
        "latency_ms": {p: lat[p] for p in pcts},
        "queue_wait_ms": {p: snap["queue_wait_ms"][p] for p in pcts},
        "engine_service_ms": {p: snap["engine_service_ms"][p] for p in pcts},
        "batch_occupancy": snap["batch_occupancy"],
        "distance_computations_per_query":
            snap["distance_computations_per_query"],
        "padding_fraction": snap["padding_fraction"],
        "n_completed": snap["n_completed"],
        "n_rejected": n_rejected,
        "n_batches": snap["n_batches"],
    }


def main(smoke: bool = False, trace_out: str | None = None) -> dict:
    tracer = None
    if trace_out:
        # the tracer's clock MUST match AnnServer's (time.monotonic):
        # per-request lane timestamps are server-clock readings emitted
        # into the tracer's time base verbatim
        tracer = Tracer(clock=time.monotonic, process="bench_serving")
        set_tracer(tracer)
    n_queries = 256
    ds = make_clustered(N_VECTORS, DIM, n_queries=n_queries, spread=1.0,
                        seed=7)
    cfg = IndexConfig(n_clusters=4, degree=16, build_degree=32,
                      block_size=512)
    merged = builder.build_scalegann(ds.data, cfg, n_workers=2)
    topo = merged.topology(ds.data)

    if smoke:
        backends = ("jax", "pallas")
        rates = (1500.0,)
        waits = (2.0, 8.0)
        max_batch, n_requests, warmup, n_batch1 = 32, 512, 256, 96
    else:
        backends = ("jax", "pallas", "numpy")
        rates = (500.0, 1500.0, 3000.0)
        waits = (0.5, 2.0, 8.0)
        max_batch, n_requests, warmup, n_batch1 = 128, 2000, 512, 256

    results: dict = {
        "fixture": {"n_vectors": N_VECTORS, "n_queries": n_queries,
                    "dim": DIM, "k": K, "width": WIDTH,
                    "max_batch": max_batch, "n_requests": n_requests,
                    "smoke": smoke},
        "batch1_blocking": {},
        "server": {},
    }

    # AnnServer pre-traces its own bucketed batch shapes at startup
    # (ServingConfig.pretrace), so trials measure steady-state serving.
    for backend in backends:
        row = bench_batch1_blocking(topo, ds, backend, n_batch1)
        results["batch1_blocking"][backend] = row
        print(f"batch1 {backend:6s} qps={row['qps']:7.0f} "
              f"recall@10={row['recall_at_10']:.3f}")

        results["server"][backend] = {}
        trials = [(None, r, w, False) for r in rates for w in waits]
        if not smoke:  # the adaptive policy rides the largest window
            trials += [(None, r, max(waits), True) for r in rates]
        if backend in ("jax", "pallas"):
            # the acceptance trials: offered load pinned to 4× the
            # *measured* jax batch-1 rate, so the ≥2× claim can't be capped
            # by a fixed offered rate on a machine with fast batch-1 calls
            # — and the pallas-vs-jax claim compares capacities under one
            # shared overload, not two different offered-rate caps
            trials.append(("rate=4x-batch1,wait=2ms",
                           4.0 * results["batch1_blocking"]["jax"]["qps"],
                           2.0, False))
        for label, rate, wait, adaptive in trials:
            row = asyncio.run(run_trial(
                topo, ds, backend=backend, rate_qps=rate, max_wait_ms=wait,
                n_requests=n_requests, max_batch=max_batch, warmup=warmup,
                adaptive=adaptive,
            ))
            if label is None:
                label = f"rate={rate:.0f}/s,wait={wait:g}ms" + \
                    (",adaptive" if adaptive else "")
            results["server"][backend][label] = row
            print(f"serve  {backend:6s} {label:32s} "
                  f"qps={row['qps']:7.0f} p95={row['latency_ms']['p95']:7.1f}ms "
                  f"(queue {row['queue_wait_ms']['p95']:6.1f} / "
                  f"engine {row['engine_service_ms']['p95']:6.1f}) "
                  f"occ={row['batch_occupancy']['mean']:5.1f} "
                  f"recall@10={row['recall_at_10']:.3f}")

    # ---- smoke only: drain one tiny trial through the force-interpret
    # Pallas kernel — the fused engine's CI-testable fallback.  Interpreter
    # pricing (~ms per query) makes it recall/coverage evidence, not a
    # throughput number; it never feeds the claims below.
    if smoke:
        from repro.kernels import pallas_mode, set_pallas_mode

        prev_mode = pallas_mode()
        set_pallas_mode("force_interpret")
        try:
            row = asyncio.run(run_trial(
                topo, ds, backend="pallas", rate_qps=100.0, max_wait_ms=8.0,
                n_requests=48, max_batch=8, warmup=8,
            ))
        finally:
            set_pallas_mode(prev_mode)
        results["server"]["pallas_interpret"] = {
            "rate=100/s,wait=8ms,interpret": row}
        print(f"serve  pallas(interpret) qps={row['qps']:7.0f} "
              f"recall@10={row['recall_at_10']:.3f}")

    # ---- acceptance: fused pallas engine beats jax on served QPS at
    # recall@10 within 0.01 (ISSUE 6) --------------------------------------
    bj = max(results["server"]["jax"].values(), key=lambda r: r["qps"])
    bp = max(results["server"]["pallas"].values(), key=lambda r: r["qps"])
    results["pallas_over_jax_qps"] = bp["qps"] / bj["qps"]
    results["claim.pallas_fused_ge_jax_qps_at_recall_within_001"] = bool(
        bp["qps"] >= bj["qps"]
        and bp["recall_at_10"] >= bj["recall_at_10"] - 0.01
    )
    print(f"pallas/jax served QPS: {bp['qps'] / bj['qps']:.2f}x "
          f"(pallas recall {bp['recall_at_10']:.3f} vs "
          f"jax {bj['recall_at_10']:.3f})")

    # ---- acceptance: micro-batching >= 2x batch-1 blocking (jax) ---------
    b1 = results["batch1_blocking"]["jax"]
    best = max(results["server"]["jax"].values(), key=lambda r: r["qps"])
    ratio = best["qps"] / b1["qps"]
    same_recall = best["recall_at_10"] >= b1["recall_at_10"] - 0.005
    results["server_over_batch1_qps_jax"] = ratio
    results["claim.server_ge_2x_batch1_blocking_at_same_recall"] = bool(
        ratio >= 2.0 and same_recall
    )
    print(f"server/batch1 QPS (jax): {ratio:.2f}x "
          f"(server recall {best['recall_at_10']:.3f} vs "
          f"batch1 {b1['recall_at_10']:.3f})")

    if tracer is not None:
        set_tracer(NULL_TRACER)
        obj = tracer.to_chrome()
        n_schema = len(validate_chrome_trace(obj))
        chk = check_serving_trace(obj)
        tracer.write(trace_out)
        results["trace"] = {
            "path": str(trace_out),
            "schema_errors": n_schema,
            "request_decomposition": chk,
        }
        print(f"trace: {trace_out} ({chk['n_requests']} request lanes, "
              f"min phase coverage {chk['min_coverage_seen']:.3f}, "
              f"schema errors {n_schema})")

    OUT_PATH.write_text(json.dumps(results, indent=2, sort_keys=True))
    print(f"wrote {OUT_PATH}")
    return results


if __name__ == "__main__":
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized run: jax+pallas, one rate, short trials, "
                         "plus a tiny force-interpret fused-engine trial")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="write a Chrome/Perfetto trace of every served "
                         "request (async lanes: queue/batch/engine/rerank)")
    args = ap.parse_args()
    main(smoke=args.smoke, trace_out=args.trace_out)
