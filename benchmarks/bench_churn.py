"""Recall-under-churn benchmark: live mutations with serving answering
throughout — writes ``BENCH_churn.json``.

The live-index claim is FreshDiskANN-shaped: a seeded insert/delete
schedule applied through :class:`repro.live.LiveIndex` — batched Vamana
insert rounds, tombstone deletes, a consolidation pass, epoch-swapped
serving — must not cost recall versus throwing the index away and
rebuilding offline on the same final point set.  Concretely:

1. Build offline on the first 70% of the fixture.
2. Drive a churn schedule: insert the remaining 30% in waves, tombstone a
   seeded mix of originals and fresh inserts, consolidate mid-stream.
   An :class:`~repro.serving.server.AnnServer` answers queries through
   the whole window; after every mutation step the server's generation is
   swapped (:meth:`~repro.serving.server.AnnServer.swap_topology`).
   Every submitted future must resolve (no rejected epochs) and no
   response may contain an id that was tombstoned at submit time.
3. Rebuild offline on exactly the surviving point set and compare
   recall@10 against exact ground truth over the live points.

The CI-guarded claim, ``claim.recall_under_churn_within_002_of_rebuild``:
churned recall@10 ≥ rebuild recall@10 − 0.02, with serving answering
throughout (every future resolved, zero tombstone leaks, ≥ 1 epoch swap
per mutation step).

    PYTHONPATH=src python benchmarks/bench_churn.py
    PYTHONPATH=src python benchmarks/bench_churn.py --smoke

``--smoke`` is the CI profile (smaller fixture, fewer queries).  Like the
other benches: run only on an otherwise-idle machine, never concurrently
with the test suite.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import pathlib

import numpy as np

from repro.common.compile_cache import enable_compile_cache
from repro.configs.base import IndexConfig
from repro.core.builder import build_scalegann
from repro.data.synthetic import exact_ground_truth, make_clustered, recall_at
from repro.live import LiveConfig, LiveIndex
from repro.search import search
from repro.serving import AnnServer, ServingConfig
from repro.telemetry import (NULL_TRACER, Tracer, current_registry,
                             set_tracer, validate_chrome_trace)

K = 10
WIDTH = 64

OUT_PATH = pathlib.Path(__file__).resolve().parent.parent / \
    "BENCH_churn.json"


def make_schedule(n_base: int, n_new: int, n_waves: int, seed: int):
    """The seeded churn schedule: per wave, one insert slice of the held-out
    points plus one delete batch mixing originals and already-inserted
    fresh points; consolidation fires at the midpoint."""
    rng = np.random.default_rng(seed)
    ins_slices = np.array_split(np.arange(n_new), n_waves)
    kill_base = rng.choice(n_base, size=n_base // 10, replace=False)
    kill_waves = np.array_split(kill_base, n_waves)
    steps = []
    for w in range(n_waves):
        dele = [n_base + i for i in ins_slices[w][: len(ins_slices[w]) // 8]]
        steps.append({
            "insert": ins_slices[w],
            "delete": np.concatenate(
                [kill_waves[w], np.asarray(dele, np.int64)]
            ),
            "consolidate": w == n_waves // 2,
        })
    return steps


async def churn_with_serving(li: LiveIndex, new_points: np.ndarray,
                             steps, queries: np.ndarray,
                             backend: str) -> dict:
    """Apply the schedule while an AnnServer answers; returns serving-side
    outcome counts (the "no rejected epochs" half of the claim)."""
    cfg = ServingConfig(backend=backend, k=K, width=WIDTH, max_batch=16,
                        max_wait_ms=0.5, pretrace=False)
    stats = {"n_queries": 0, "n_resolved": 0, "n_failed": 0,
             "tombstone_leaks": 0, "n_swaps": 0}
    deleted: set[int] = set()
    async with AnnServer(li.snapshot(), config=cfg) as srv:
        for step in steps:
            # a wave of traffic is in flight while the mutation lands
            dead_at_submit = frozenset(deleted)
            wave = [srv.submit_nowait(q) for q in queries]
            await asyncio.sleep(0)  # let batches start flushing
            if len(step["insert"]):
                li.insert_batch(new_points[step["insert"]])
            if len(step["delete"]):
                li.delete_batch(np.asarray(step["delete"], np.int64))
                deleted.update(int(i) for i in step["delete"])
            if step["consolidate"]:
                li.consolidate()
            srv.swap_topology(li.snapshot())
            stats["n_swaps"] += 1
            results = await asyncio.gather(*wave, return_exceptions=True)
            for r in results:
                stats["n_queries"] += 1
                if isinstance(r, BaseException):
                    stats["n_failed"] += 1
                    continue
                stats["n_resolved"] += 1
                if set(int(i) for i in r.ids) & dead_at_submit:
                    stats["tombstone_leaks"] += 1
        # post-churn wave on the final generation: nothing deleted may
        # ever come back
        final = await asyncio.gather(
            *[srv.submit(q) for q in queries]
        )
        for r in final:
            stats["n_queries"] += 1
            stats["n_resolved"] += 1
            if set(int(i) for i in r.ids) & deleted:
                stats["tombstone_leaks"] += 1
        stats["server_rejected"] = srv.stats.n_rejected
        stats["server_failed"] = srv.stats.n_failed
    return stats


def main(smoke: bool = False, trace_out: str | None = None) -> dict:
    tracer = None
    if trace_out:
        tracer = Tracer(process="bench_churn")
        set_tracer(tracer)
    n = 1200 if smoke else 4000
    dim = 16 if smoke else 32
    n_queries = 48 if smoke else 128
    n_waves = 4 if smoke else 8
    backend = "numpy" if smoke else "jax"
    n_base = int(n * 0.7)
    cfg = IndexConfig(n_clusters=4 if smoke else 8, degree=16,
                      build_degree=32)

    ds = make_clustered(n, dim, n_queries=n_queries, gt_k=K, seed=0)
    base, held_out = ds.data[:n_base], ds.data[n_base:]

    print(f"== offline build on {n_base} of {n} vectors ==")
    li = LiveIndex.from_build(
        build_scalegann(base, cfg, algo="vamana"), base, cfg,
        LiveConfig(backend=backend),
    )
    steps = make_schedule(n_base, len(held_out), n_waves, seed=1)

    print(f"== churn: {n_waves} waves of insert/delete under live "
          f"serving ({backend}) ==")
    serving = asyncio.run(
        churn_with_serving(li, held_out, steps, ds.queries, backend)
    )
    print(f"  {serving['n_resolved']}/{serving['n_queries']} futures "
          f"resolved, {serving['n_swaps']} epoch swaps, "
          f"{serving['tombstone_leaks']} tombstone leaks, "
          f"{serving['server_rejected']} rejected")
    li.consolidate()  # end-of-window pass: everything dead goes physical

    deleted = sorted({int(i) for s in steps for i in s["delete"]})
    live_ids = np.asarray(
        sorted(set(range(li.n_vectors)) - set(deleted)), np.int64
    )
    gt = live_ids[exact_ground_truth(li._data[live_ids], ds.queries, K)]

    ids_live, st_live = search(li.snapshot(), ds.queries, K, width=WIDTH,
                               backend=backend)
    recall_live = recall_at(ids_live, gt, K)

    print("== fresh offline rebuild on the surviving point set ==")
    rebuilt = build_scalegann(li._data[live_ids], cfg, algo="vamana")
    ids_re, st_re = search(rebuilt.shard_topology(li._data[live_ids]),
                           ds.queries, K, width=WIDTH, backend=backend)
    recall_rebuild = recall_at(live_ids[ids_re], gt, K)

    served_ok = (
        serving["n_resolved"] == serving["n_queries"]
        and serving["n_failed"] == 0
        and serving["server_rejected"] == 0
        and serving["tombstone_leaks"] == 0
        and serving["n_swaps"] >= n_waves
    )
    claim = bool(recall_live >= recall_rebuild - 0.02 and served_ok)

    reg = current_registry()
    snap = reg.snapshot() if hasattr(reg, "snapshot") else {}
    live_metrics = {
        k: v for k, v in (snap.items() if isinstance(snap, dict) else [])
        if str(k).startswith("live_")
    }

    trace_block = None
    if tracer is not None:
        set_tracer(NULL_TRACER)
        obj = tracer.to_chrome()
        n_schema = len(validate_chrome_trace(obj))
        tracer.write(trace_out)
        trace_block = {"path": str(trace_out), "schema_errors": n_schema}
        print(f"trace: {trace_out} (schema errors {n_schema})")

    results = {
        "fixture": {"n": n, "dim": dim, "n_base": n_base,
                    "n_queries": n_queries, "n_waves": n_waves,
                    "backend": backend, "smoke": smoke},
        "churn": {
            "n_inserted": int(len(held_out)),
            "n_deleted": len(deleted),
            "final_live": int(len(live_ids)),
            "generations": li.generation,
            "n_shards": li.n_shards,
            "insert_distance_computations": li.n_distance_computations,
        },
        "serving": serving,
        "recall_at_10_churned": recall_live,
        "recall_at_10_rebuild": recall_rebuild,
        "recall_gap": recall_rebuild - recall_live,
        "distance_computations_per_query_churned":
            st_live.per_query()["distance_computations"],
        "distance_computations_per_query_rebuild":
            st_re.per_query()["distance_computations"],
        "live_metrics": live_metrics,
        "claim.recall_under_churn_within_002_of_rebuild": claim,
    }
    if trace_block is not None:
        results["trace"] = trace_block
    OUT_PATH.write_text(json.dumps(results, indent=2, default=float))
    print(f"\nrecall@10 churned {recall_live:.3f} vs rebuild "
          f"{recall_rebuild:.3f} (gap {recall_rebuild - recall_live:+.3f}, "
          f"allowed 0.02); serving ok {served_ok} -> claim {claim}")
    print(f"wrote {OUT_PATH}")
    return results


if __name__ == "__main__":
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="CI profile: smaller fixture, fewer queries")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="write a Chrome/Perfetto trace of the churn window "
                         "(mutation spans + serving request lanes)")
    args = ap.parse_args()
    main(smoke=args.smoke, trace_out=args.trace_out)
