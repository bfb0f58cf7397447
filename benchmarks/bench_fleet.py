"""Spot-fleet build benchmark: preemption-tolerant real builds + the
simulated policy/price comparison — writes ``BENCH_fleet.json``.

Two halves, matching the paper's §IV/§VI-C claim structure:

1. **Real executor** (the robustness claim): ``build_scalegann_fleet``
   runs actual per-shard ``build_shard_index_vamana`` tasks with an
   injected mid-shard kill; the build checkpoints at round grain,
   re-queues, resumes, and must finish with recall@10 within 0.01 of an
   uninterrupted ``build_scalegann`` (on this executor the per-shard
   graphs are bit-identical, so the recalls are equal — both recorded).

2. **Simulated fleet** (the price claim): the virtual-clock ``Scheduler``
   packs a Laion-scale task list onto spot vs on-demand pools under both
   scheduling policies (cost-greedy and deadline/EDD), with task runtimes
   from a model **calibrated on tiny real builds** (paper §IV — no
   hand-set constants) and prices from the §VI-C cost model.  Task sizes
   are chosen so one shard fits the §II-B protected hour, the same
   feasibility constraint the paper's time-based policy enforces.

The CI-guarded claim, ``claim.spot_cheaper_than_ondemand_at_recall_parity``:
the best spot-policy cost beats the best on-demand cost while the
preempted real build holds recall parity (and ≥ 1 kill actually fired).

    PYTHONPATH=src python benchmarks/bench_fleet.py
    PYTHONPATH=src python benchmarks/bench_fleet.py --smoke

``--smoke`` is the CI profile: fewer recall-eval queries and a smaller
simulated fleet; the real-executor half keeps its full shape (it *is* the
measurement).  Like the other benches: run only on an otherwise-idle
machine, never concurrently with the test suite.
"""

from __future__ import annotations

import argparse
import json
import pathlib

import numpy as np

from repro.common.compile_cache import enable_compile_cache
from repro.configs.base import IndexConfig
from repro.core import cost_model
from repro.core.builder import build_scalegann
from repro.core.scheduler import (V100_ONDEMAND, V100_SPOT, DeadlinePolicy,
                                  Scheduler, Task, calibrate_runtime,
                                  make_ondemand_pool)
from repro.data.synthetic import make_clustered, recall_at
from repro.fleet import (SCHEDULING_POLICIES, CheckpointStore,
                         PreemptionInjector, build_scalegann_fleet)
from repro.telemetry import (NULL_TRACER, Tracer, check_fleet_trace,
                             set_tracer, validate_chrome_trace)

N_VECTORS = 2000
DIM = 32
K = 10
WIDTH = 64
SHARD_BYTES = 16e9  # §VI-C: one shard task moves ≤ the HBM cap each way

OUT_PATH = pathlib.Path(__file__).resolve().parent.parent / \
    "BENCH_fleet.json"


def bench_real_executor(ds, cfg, model, *, n_queries: int) -> dict:
    """One uninterrupted build vs one build with an injected mid-shard
    kill — checkpoint/resume must preserve the index."""
    queries, gt = ds.queries[:n_queries], ds.gt[:n_queries]

    plain = build_scalegann(ds.data, cfg, algo="vamana")
    pids, _ = plain.search(ds.data, queries, K, backend="jax", width=WIDTH)
    recall_plain = recall_at(pids, gt, K)

    injector = PreemptionInjector(kill_shard_at={0: 2, 1: 3})
    store = CheckpointStore()
    out = build_scalegann_fleet(
        ds.data, cfg, n_workers=2, injector=injector, runtime_model=model,
        checkpoint_store=store,
    )
    fids, _ = out.build.search(ds.data, queries, K, backend="jax",
                               width=WIDTH)
    recall_fleet = recall_at(fids, gt, K)
    graphs_identical = all(
        np.array_equal(a, b)
        for a, b in zip(out.build.shard_graphs, plain.shard_graphs)
    )
    r = out.report
    return {
        "n_shards": r.n_shards,
        "n_preemptions": r.n_preemptions,
        "n_resumes": r.n_resumes,
        "n_requeues": r.n_requeues,
        "n_checkpoint_saves": store.n_saves,
        "rounds_completed": r.rounds_completed,
        "rounds_lost": r.rounds_lost,
        "shard_attempts": r.shard_attempts,
        "recall_uninterrupted": recall_plain,
        "recall_interrupted": recall_fleet,
        "graphs_identical_to_uninterrupted": graphs_identical,
        "makespan_s": r.makespan_s,
        "accelerator_active_s": r.accelerator_active_s,
        "cost_usd": r.cost.total,
    }


def make_staggered_spot_pool(n_instances: int) -> list:
    """Deterministic spot pool: lifetimes staggered past the §II-B
    protected hour so terminations land *during* the build, plus one
    long-lived survivor so the task list always finishes (the virtual
    Scheduler never relaunches — a fully dead pool is unschedulable)."""
    from repro.core.scheduler import Instance

    safe = V100_SPOT.safe_duration_s
    pool = [
        Instance(iid=i, itype=V100_SPOT, launched_at=0.0,
                 lifetime_s=safe + 100.0 + 900.0 * i)
        for i in range(n_instances - 1)
    ]
    pool.append(Instance(iid=n_instances - 1, itype=V100_SPOT,
                         launched_at=0.0, lifetime_s=24 * 3600.0))
    return pool


def simulate_policy(model, sizes, *, spot: bool, policy_name: str,
                    n_instances: int) -> dict:
    """Virtual-clock fleet: same task list, spot or on-demand pool, one
    scheduling policy — makespan + §VI-C dollars."""
    policy = SCHEDULING_POLICIES[policy_name]()
    itype = V100_SPOT if spot else V100_ONDEMAND
    tasks = [Task(tid=i, shard=i, size=int(s)) for i, s in enumerate(sizes)]
    if isinstance(policy, DeadlinePolicy):
        for t in tasks:  # EDD needs due dates: 3× the calibrated estimate
            t.deadline_s = 3.0 * model.estimate(t.size, itype)
    pool = (
        make_staggered_spot_pool(n_instances)
        if spot else make_ondemand_pool(n_instances)
    )
    sim = Scheduler(
        tasks, pool, model, policy=policy,
        checkpoint_resume=True, checkpoint_interval_s=60.0,
    ).run()
    cost = cost_model.fleet_cost(
        sim.makespan_s, sim.gpu_active_s, len(sizes), SHARD_BYTES,
        accel=itype,
    )
    return {
        "instance_type": itype.name,
        "n_instances": n_instances,
        "makespan_s": sim.makespan_s,
        "gpu_active_s": sim.gpu_active_s,
        "n_preemptions": sim.n_preemptions,
        "n_restarts": sim.n_restarts,
        "work_lost_s": sim.work_lost_s,
        "cost_usd": cost.total,
        "cost_cpu_usd": cost.cpu_cost,
        "cost_accelerator_usd": cost.accelerator_cost,
    }


def main(smoke: bool = False, trace_out: str | None = None) -> dict:
    tracer = None
    if trace_out:
        # installed process-wide so the executor's worker/shard tracks AND
        # the per-round vamana spans land on one timeline
        tracer = Tracer(process="bench_fleet")
        set_tracer(tracer)
    n_queries = 32 if smoke else 128
    ds = make_clustered(N_VECTORS, DIM, n_queries=128, spread=1.0, seed=0)
    cfg = IndexConfig(n_clusters=4, degree=16, build_degree=32,
                      block_size=1024)

    print("== calibrating runtime model on tiny real vamana builds ==")
    model = calibrate_runtime(None, ds.data, (256, 512, 1024), cfg=cfg,
                              backend="numpy")
    print(f"  {model.seconds_per_vector * 1e6:.1f} µs/vector "
          f"+ {model.fixed_overhead_s:.3f}s overhead")

    print("== real executor: injected kill, checkpoint/resume ==")
    real = bench_real_executor(ds, cfg, model, n_queries=n_queries)
    print(f"  {real['n_preemptions']} preemption(s), "
          f"{real['n_resumes']} resume(s), recall "
          f"{real['recall_interrupted']:.3f} vs uninterrupted "
          f"{real['recall_uninterrupted']:.3f} "
          f"(graphs identical: {real['graphs_identical_to_uninterrupted']})")

    print("== simulated fleet: policies × spot/on-demand ==")
    # Laion-scale task list: each shard's estimated runtime fits well
    # inside the §II-B protected hour (time-based feasibility), and the
    # total work outlives the earliest spot terminations so preemption +
    # re-allocation is actually exercised
    n_shards = 16 if smoke else 48
    n_instances = 4 if smoke else 8
    rng = np.random.default_rng(0)
    est_s = rng.uniform(600.0, 1500.0, n_shards)
    sizes = ((est_s - model.fixed_overhead_s)
             / model.seconds_per_vector).astype(np.int64)
    sim: dict = {}
    for policy_name in SCHEDULING_POLICIES:
        sim[policy_name] = {}
        for spot in (True, False):
            row = simulate_policy(
                model, sizes, spot=spot, policy_name=policy_name,
                n_instances=n_instances,
            )
            sim[policy_name]["spot" if spot else "ondemand"] = row
            print(f"  {policy_name:12s} {'spot' if spot else 'ondemand':9s}"
                  f" makespan {row['makespan_s']:8.0f}s  "
                  f"${row['cost_usd']:7.2f}  "
                  f"({row['n_preemptions']} preemptions, "
                  f"{row['work_lost_s']:.0f}s lost)")

    best_spot = min(sim[p]["spot"]["cost_usd"] for p in sim)
    best_od = min(sim[p]["ondemand"]["cost_usd"] for p in sim)
    recall_parity = abs(
        real["recall_interrupted"] - real["recall_uninterrupted"]
    ) <= 0.01
    claim = bool(
        best_spot < best_od
        and recall_parity
        and real["n_preemptions"] >= 1
        and real["n_resumes"] >= 1
    )
    trace_block = None
    if tracer is not None:
        set_tracer(NULL_TRACER)
        obj = tracer.to_chrome()
        n_schema = len(validate_chrome_trace(obj))
        chk = check_fleet_trace(obj)
        tracer.write(trace_out)
        trace_block = {
            "path": str(trace_out),
            "schema_errors": n_schema,
            "preemption_lifecycle": chk,
        }
        print(f"trace: {trace_out} ({chk['n_attempt_spans']} attempt "
              f"spans, {chk['n_kills']} kills, {chk['n_resumes']} resumes; "
              f"lifecycle ok {chk['ok']}, schema errors {n_schema})")

    results = {
        "fixture": {"n": N_VECTORS, "dim": DIM, "n_queries": n_queries,
                    "smoke": smoke},
        "runtime_model": {
            "seconds_per_vector": model.seconds_per_vector,
            "fixed_overhead_s": model.fixed_overhead_s,
            "calibrated_from": "real vectorized vamana sample builds",
        },
        "real_executor": real,
        "simulated": sim,
        "spot_over_ondemand_cost": best_spot / best_od,
        "claim.spot_cheaper_than_ondemand_at_recall_parity": claim,
    }
    if trace_block is not None:
        results["trace"] = trace_block
    OUT_PATH.write_text(json.dumps(results, indent=2, default=float))
    print(f"\nspot/on-demand cost = {best_spot / best_od:.2f}x "
          f"(${best_spot:.2f} vs ${best_od:.2f}), recall parity "
          f"{recall_parity} -> claim {claim}")
    print(f"wrote {OUT_PATH}")
    return results


if __name__ == "__main__":
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="CI profile: fewer queries, smaller simulation")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="write a Chrome/Perfetto trace of the fleet build "
                         "(worker attempt spans, kill/backoff/resume)")
    args = ap.parse_args()
    main(smoke=args.smoke, trace_out=args.trace_out)
