"""Crash-recovery benchmark: a seeded crash schedule against the live
durability layer — writes ``BENCH_durability.json``.

The durability claim is the whole point of pricing the system around
preemptible capacity: kill -9 at any byte boundary must cost *nothing*
but replay time.  Concretely:

1. Build offline on 70% of the fixture; run a seeded insert/delete/
   consolidate schedule **twice** — once purely in memory (the uncrashed
   reference), once durably (``LiveIndex.save`` + WAL) under a seeded
   :class:`~repro.durability.CrashInjector` schedule of ≥3 crashes at
   distinct crash points, including a **torn append**, a **pre-fsync
   power loss**, an **interrupted snapshot commit** (crash between
   tmp-write and rename of ``CURRENT``), and a **mid-replay kill**
   during recovery itself.
2. After every crash the driver drops the in-memory index, recovers with
   ``LiveIndex.load`` (snapshot restore + WAL tail replay), and resumes
   the schedule at the position the recovered ``wal_seq`` proves was
   durably applied — re-running any acked-but-unsynced mutations, which
   is exactly the deterministic-replay contract.
3. The recovered index is compared against the uncrashed reference
   **served**, not just diffed: direct ``search`` ids must be identical
   across backend × dtype, and an :class:`~repro.serving.AnnServer`
   answering live traffic must return identical ids after
   ``swap_topology(..., reason="recovery")``.

The CI-guarded claim, ``claim.recovered_ids_identical_to_uncrashed``:
every backend × dtype combination returns bit-identical top-k ids, the
epoch-swapped serving wave resolves every future with identical ids,
and the injector delivered ≥3 crashes at ≥3 distinct points (torn
append and mid-replay among them).

    PYTHONPATH=src python benchmarks/bench_durability.py
    PYTHONPATH=src python benchmarks/bench_durability.py --smoke

``--smoke`` is the CI profile.  Like the other benches: run only on an
otherwise-idle machine, never concurrently with the test suite.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import pathlib
import shutil
import tempfile

import numpy as np

from repro.common.compile_cache import enable_compile_cache
from repro.configs.base import IndexConfig
from repro.data.synthetic import make_clustered
from repro.core.builder import build_scalegann
from repro.durability import CrashInjector, SimulatedCrash
from repro.live import LiveConfig, LiveIndex
from repro.search import search
from repro.serving import AnnServer, ServingConfig
from repro.telemetry import (NULL_TRACER, Tracer, check_durability_trace,
                             current_registry, set_tracer,
                             validate_chrome_trace)

K = 10
WIDTH = 64

OUT_PATH = pathlib.Path(__file__).resolve().parent.parent / \
    "BENCH_durability.json"

#: the seeded crash schedule — ≥3 distinct points, including the
#: acceptance-mandated torn write and mid-replay kill.  Hit counts are
#: 1-based occurrence indices of each crash point.
CRASH_SCHEDULE = {
    "wal.append.torn": 2,            # tear the 2nd logged mutation
    "replay.record": 1,              # die during the recovery that follows
    "wal.append.pre_fsync": 6,       # later: lose the unsynced window
    "snapshot.current.pre_rename": 2,  # kill the mid-run snapshot commit
}


def make_schedule(n_base: int, n_new: int, n_waves: int, seed: int):
    """Per wave: one insert slice + one seeded delete batch; one
    consolidation at the midpoint.  Same shape as the churn bench so the
    mutation mix is representative."""
    rng = np.random.default_rng(seed)
    ins = np.array_split(np.arange(n_new), n_waves)
    kills = np.array_split(
        rng.choice(n_base, size=n_base // 10, replace=False), n_waves)
    steps = []
    for w in range(n_waves):
        steps.append(("insert", ins[w]))
        steps.append(("delete", kills[w]))
        if w == n_waves // 2:
            steps.append(("consolidate", None))
    return steps


def apply_step(li: LiveIndex, step, new_points: np.ndarray) -> None:
    op, arg = step
    if op == "insert":
        li.insert_batch(new_points[arg])
    elif op == "delete":
        li.delete_batch(np.asarray(arg, np.int64))
    else:
        li.consolidate()


def crashed_run(base, cfg, live_cfg, steps, new_points, root,
                injector, *, fsync_interval: int = 2):
    """The durable run: baseline save, schedule under injected crashes,
    mid-run save, recover-and-resume after every kill."""
    def boot():
        return LiveIndex.from_build(
            build_scalegann(base, cfg, algo="vamana"), base, cfg, live_cfg)

    def recover():
        while True:
            try:
                return LiveIndex.load(root, cfg, live_cfg,
                                      fsync_interval=fsync_interval,
                                      injector=injector)
            except SimulatedCrash:
                pass  # mid-replay kill: recovery is crash-safe, go again

    li = boot()
    li.save(root, fsync_interval=fsync_interval, injector=injector)
    seq0 = li.wal_seq
    mid_save_at, mid_saved = len(steps) // 2, False
    pos = recoveries = 0
    while pos < len(steps):
        try:
            if pos >= mid_save_at and not mid_saved:
                li.save(root, injector=injector)
                mid_saved = True
            apply_step(li, steps[pos], new_points)
            pos += 1
        except SimulatedCrash:
            recoveries += 1
            assert recoveries <= 50, "crash/recover livelock"
            li = recover()
            pos = li.wal_seq - seq0
    li.close()
    return recover(), recoveries  # final state re-read from disk


async def serve_comparison(topo_ref, topo_rec, queries, backend) -> dict:
    """E2E: one server answers a wave on the uncrashed generation, epoch-
    swaps to the recovered one (reason="recovery"), answers the same
    wave again — ids must match wave-for-wave."""
    cfg = ServingConfig(backend=backend, k=K, width=WIDTH, max_batch=16,
                        max_wait_ms=0.5, pretrace=False)
    out = {"n_queries": 0, "n_resolved": 0, "ids_identical": True}
    async with AnnServer(topo_ref, config=cfg) as srv:
        ref = await asyncio.gather(*[srv.submit(q) for q in queries])
        srv.swap_topology(topo_rec, reason="recovery")
        rec = await asyncio.gather(*[srv.submit(q) for q in queries])
        for a, b in zip(ref, rec):
            out["n_queries"] += 2
            out["n_resolved"] += 2
            if not np.array_equal(a.ids, b.ids):
                out["ids_identical"] = False
        out["server_rejected"] = srv.stats.n_rejected
        out["server_failed"] = srv.stats.n_failed
        out["generation"] = srv.topology_generation
    return out


def main(smoke: bool = False, trace_out: str | None = None) -> dict:
    tracer = None
    if trace_out:
        tracer = Tracer(process="bench_durability")
        set_tracer(tracer)
    n = 900 if smoke else 2400
    dim = 16 if smoke else 32
    n_queries = 32 if smoke else 96
    n_waves = 4 if smoke else 6
    n_base = int(n * 0.7)
    cfg = IndexConfig(n_clusters=4 if smoke else 8, degree=16,
                      build_degree=32)
    live_cfg = LiveConfig(backend="numpy")
    combos = [("numpy", "f32"), ("numpy", "uint8"),
              ("jax", "f32"), ("jax", "uint8")]

    ds = make_clustered(n, dim, n_queries=n_queries, gt_k=K, seed=0)
    base, held_out = ds.data[:n_base], ds.data[n_base:]
    steps = make_schedule(n_base, len(held_out), n_waves, seed=1)

    print(f"== uncrashed reference: offline build on {n_base} + "
          f"{len(steps)} mutations in memory ==")
    ref = LiveIndex.from_build(
        build_scalegann(base, cfg, algo="vamana"), base, cfg, live_cfg)
    for step in steps:
        apply_step(ref, step, held_out)
    topo_ref = ref.snapshot()

    root = pathlib.Path(tempfile.mkdtemp(prefix="bench_durability_"))
    injector = CrashInjector(crash_at=dict(CRASH_SCHEDULE))
    print(f"== crashed run: same schedule under {len(CRASH_SCHEDULE)} "
          f"scheduled kills ({', '.join(CRASH_SCHEDULE)}) ==")
    rec, recoveries = crashed_run(base, cfg, live_cfg, steps, held_out,
                                  root, injector)
    topo_rec = rec.snapshot()
    points_hit = sorted(injector.crash_points_hit)
    print(f"  {injector.n_crashes} crashes delivered at {points_hit}, "
          f"{recoveries} recoveries, final wal_seq {rec.wal_seq}")

    per_combo = {}
    for backend, dtype in combos:
        ids_a, _ = search(topo_ref, ds.queries, K, width=WIDTH,
                          backend=backend, dtype=dtype)
        ids_b, _ = search(topo_rec, ds.queries, K, width=WIDTH,
                          backend=backend, dtype=dtype)
        per_combo[f"{backend}/{dtype}"] = bool(np.array_equal(ids_a, ids_b))
        print(f"  {backend}/{dtype}: recovered ids identical = "
              f"{per_combo[f'{backend}/{dtype}']}")

    print("== served comparison across the recovery epoch swap ==")
    serving = asyncio.run(
        serve_comparison(topo_ref, topo_rec, ds.queries, "numpy"))
    print(f"  {serving['n_resolved']}/{serving['n_queries']} futures "
          f"resolved, served ids identical = {serving['ids_identical']}")

    crash_coverage = (
        injector.n_crashes >= 3
        and len(points_hit) >= 3
        and "wal.append.torn" in points_hit
        and "replay.record" in points_hit
    )
    claim = bool(
        all(per_combo.values())
        and serving["ids_identical"]
        and serving["n_resolved"] == serving["n_queries"]
        and serving["server_rejected"] == 0
        and serving["server_failed"] == 0
        and crash_coverage
    )

    reg = current_registry()
    snap = reg.snapshot() if hasattr(reg, "snapshot") else {}
    durability_metrics = {
        k: v for k, v in (snap.items() if isinstance(snap, dict) else [])
        if str(k).startswith(("wal_", "recovery_", "snapshot_",
                              "serving_topology_swaps"))
    }

    trace_block = None
    if tracer is not None:
        set_tracer(NULL_TRACER)
        obj = tracer.to_chrome()
        n_schema = len(validate_chrome_trace(obj))
        lifecycle = check_durability_trace(obj, min_crashes=3)
        tracer.write(trace_out)
        trace_block = {"path": str(trace_out), "schema_errors": n_schema,
                       "lifecycle": lifecycle}
        print(f"trace: {trace_out} (schema errors {n_schema}, lifecycle "
              f"ok {lifecycle['ok']})")

    results = {
        "fixture": {"n": n, "dim": dim, "n_base": n_base,
                    "n_queries": n_queries, "n_waves": n_waves,
                    "n_steps": len(steps), "smoke": smoke},
        "crash_schedule": CRASH_SCHEDULE,
        "crashes": {
            "n_crashes": injector.n_crashes,
            "points_hit": points_hit,
            "events": [list(e) for e in injector.events],
            "n_recoveries": recoveries,
            "includes_torn_write": "wal.append.torn" in points_hit,
            "includes_mid_replay": "replay.record" in points_hit,
        },
        "recovered": {
            "wal_seq": rec.wal_seq,
            "generation": rec.generation,
            "n_vectors": rec.n_vectors,
            "n_live": rec.n_live,
            "n_shards": rec.n_shards,
        },
        "ids_identical_per_combo": per_combo,
        "serving": serving,
        "durability_metrics": durability_metrics,
        "claim.recovered_ids_identical_to_uncrashed": claim,
    }
    if trace_block is not None:
        results["trace"] = trace_block
    OUT_PATH.write_text(json.dumps(results, indent=2, default=float))
    print(f"\n{injector.n_crashes} crashes at {len(points_hit)} distinct "
          f"points; identical ids across {len(combos)} backend×dtype "
          f"combos = {all(per_combo.values())}; served identical = "
          f"{serving['ids_identical']} -> claim {claim}")
    print(f"wrote {OUT_PATH}")
    rec.close()
    shutil.rmtree(root, ignore_errors=True)
    return results


if __name__ == "__main__":
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="CI profile: smaller fixture, fewer queries")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="write a Chrome/Perfetto trace of the crash/"
                         "recover lifecycle (durability track)")
    args = ap.parse_args()
    main(smoke=args.smoke, trace_out=args.trace_out)
