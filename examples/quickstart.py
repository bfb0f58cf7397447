"""Quickstart: build a ScaleGANN index and serve queries — 60 seconds.

Sections: 1–3 build, 4 query backends, 5 routed split serving, 6 the
micro-batching server, 7 quantized distance stages (uint8/bf16 + f32
re-rank), 8 vectorized vs seed-loop build timing, 9 the fused
device-resident beam engine (backend="pallas"), 10 preemption-tolerant
spot-fleet builds (checkpoint/resume through an injected kill), traced
end-to-end with the telemetry subsystem (README §10 — open the written
trace at https://ui.perfetto.dev), 11 the live mutable index
(insert/delete/search under churn with epoch-swapped serving), 12
crash-consistent durability (WAL + atomic snapshots: kill the process
mid-mutation, recover, serve identical ids).

    PYTHONPATH=src python examples/quickstart.py
"""

import asyncio
import time

import numpy as np

from repro.common.compile_cache import enable_compile_cache
from repro.configs.base import IndexConfig
from repro.core.builder import build_scalegann
from repro.core.merge import connectivity_stats
from repro.data.synthetic import make_clustered, recall_at
from repro.search import search
from repro.serving import AnnServer, ServerStats, ServingConfig


def main():
    # 1. a clustered vector dataset (stand-in for Sift/Laion embeddings)
    ds = make_clustered(5000, 64, n_queries=50, spread=1.0, seed=0)

    # 2. paper knobs: k-means shards, selective replication ε, degree R
    cfg = IndexConfig(n_clusters=8, degree=16, build_degree=32,
                      epsilon=1.2, block_size=1024)

    # 3. partition → parallel shard builds → merge (n_workers ≈ #GPUs)
    res = build_scalegann(ds.data, cfg, n_workers=4)
    print(f"partition {res.partition_s:.2f}s | shard builds "
          f"{res.wall_build_s:.2f}s (Σ {res.build_only_s:.2f}s) | "
          f"merge {res.merge_s:.2f}s")
    print(f"replicated {res.stats['replica_proportion']:.1%} of vectors "
          f"(DiskANN uniform would be ~100%)")
    print("connectivity:", connectivity_stats(res.index))

    # 4. CPU serving (paper: queries never touch accelerators).  The same
    #    repro.search call serves any topology with any backend: "numpy" is
    #    the latency-shaped reference, "jax" the batched throughput engine.
    for backend in ("numpy", "jax"):
        ids, stats = search(res.index, ds.queries, k=10, data=ds.data,
                            backend=backend, width=96)
        print(f"[{backend}] recall@10 = {recall_at(ids, ds.gt, 10):.3f}  "
              f"({stats.n_distance_computations / len(ds.queries):.0f} "
              f"distance computations / query)")

    # 5. Routed split serving: the partition's replicated shards can be
    #    served directly (no merge), routing each query to its nprobe
    #    nearest shard centroids instead of broadcasting to all of them.
    shard_topo = res.shard_topology(ds.data)
    for nprobe in (None, 2, "auto"):
        ids, stats = search(shard_topo, ds.queries, k=10, backend="jax",
                            width=96, nprobe=nprobe)
        label = "scatter-all" if nprobe is None else f"nprobe={nprobe}"
        print(f"[shards/{label}] recall@10 = "
              f"{recall_at(ids, ds.gt, 10):.3f}  "
              f"({stats.n_distance_computations / len(ds.queries):.0f} "
              f"distance computations / query)")

    # 6. Serving: single-query traffic goes through repro.serving, which
    #    micro-batches submit() calls into engine-sized search() batches
    #    (flush at max_batch or max_wait_ms, whichever first).  See
    #    examples/serve_ann.py for the open-loop load-generator version.
    async def serve_a_few():
        sc = ServingConfig(backend="jax", k=10, width=96, max_batch=32,
                           max_wait_ms=2.0)
        async with AnnServer(res.index, data=ds.data, config=sc) as srv:
            # first round absorbs the server's startup (jit pretrace of
            # its batch shapes); then measure a steady round
            await asyncio.gather(*(srv.submit(q) for q in ds.queries))
            srv.stats = ServerStats()
            outs = await asyncio.gather(
                *(srv.submit(q) for q in ds.queries)
            )
        ids = np.stack([o.ids for o in outs])
        snap = srv.stats.snapshot()
        print(f"[served] recall@10 = {recall_at(ids, ds.gt, 10):.3f}  "
              f"p95 = {snap['latency_ms']['p95']:.1f} ms  "
              f"mean batch = {snap['batch_occupancy']['mean']:.1f}")

    asyncio.run(serve_a_few())

    # 7. Quantized distance stages: traverse the graph on cheap uint8 (or
    #    bf16) distances — 4× (2×) less memory traffic per scored pair —
    #    then re-rank the top rerank·k candidates exactly in f32.  Specs
    #    (scale/zero-point) are learned per shard from the partitioner's
    #    data pass; stats split the quantized vs re-rank work.
    for dt in ("f32", "bf16", "uint8"):
        ids, stats = search(shard_topo, ds.queries, k=10, backend="jax",
                            width=96, nprobe=2, dtype=dt, rerank=4)
        pq = stats.per_query()
        print(f"[dtype={dt:5s}] recall@10 = "
              f"{recall_at(ids, ds.gt, 10):.3f}  "
              f"({pq['distance_computations']:.0f} dist/q: "
              f"{pq['quantized_distance_computations']:.0f} quantized + "
              f"{pq['rerank_distance_computations']:.0f} f32 re-rank)")

    # 8. The build itself is vectorized (the paper's headline is *build*
    #    acceleration): Vamana inserts in engine-backed batched rounds,
    #    CAGRA's prune and the merge run sort-based vector passes.  The
    #    seed-loop baselines survive behind reference=True — compare them
    #    on a slice (the full BENCH_build.json matrix: bench_build.py,
    #    which also documents the ≥5x CI-guarded claim and the --scale
    #    large 10^5 memmapped profile):
    sub = ds.data[:800]
    t0 = time.perf_counter()
    build_scalegann(sub, cfg, algo="vamana", reference=True)
    t_ref = time.perf_counter() - t0
    build_scalegann(sub, cfg, algo="vamana")  # warm: first build pays
    t0 = time.perf_counter()                  # the one-off jit trace
    build_scalegann(sub, cfg, algo="vamana")
    t_vec = time.perf_counter() - t0
    print(f"[build] seed-loop vamana {t_ref:.2f}s -> vectorized "
          f"{t_vec:.2f}s ({t_ref / t_vec:.1f}x on this slice)")

    # 9. The fused beam engine: backend="pallas" runs the whole search —
    #    seed scoring, beam traversal, top-k upkeep, and (for staged
    #    dtypes) the exact-f32 re-rank — as ONE dispatch per batch, with
    #    candidate state resident in VMEM on TPU (a flat-batch XLA twin
    #    serves CPU hosts, same answers).  Ids match the jax backend
    #    bit-for-bit, so it drops into any search()/AnnServer call site;
    #    BENCH_serving.json records it beating jax on served QPS.
    jids, jstats = search(res.index, ds.queries, k=10, data=ds.data,
                          backend="jax", width=96)
    pids, pstats = search(res.index, ds.queries, k=10, data=ds.data,
                          backend="pallas", width=96)
    print(f"[pallas] recall@10 = {recall_at(pids, ds.gt, 10):.3f}  "
          f"ids identical to jax: {bool(np.array_equal(pids, jids))}  "
          f"({pstats.n_distance_computations / len(ds.queries):.0f} "
          f"distance computations / query)")
    ids, stats = search(shard_topo, ds.queries, k=10, backend="pallas",
                        width=96, nprobe=2, dtype="uint8", rerank=4)
    pq = stats.per_query()
    print(f"[pallas/uint8] recall@10 = {recall_at(ids, ds.gt, 10):.3f}  "
          f"({pq['quantized_distance_computations']:.0f} quantized + "
          f"{pq['rerank_distance_computations']:.0f} f32 re-rank dist/q, "
          f"traversal+re-rank fused on the merged path)")

    # 10. Spot-fleet builds survive preemptions: build_scalegann_fleet
    #     runs the same shard builds through a scheduler that checkpoints
    #     every batched round, so a killed instance costs only the rounds
    #     since the last save — the task re-queues, resumes mid-build, and
    #     the finished index is bit-identical to an uninterrupted one.
    #     Here we inject one kill on shard 0 at round 2 and watch it heal,
    #     with a Tracer recording the whole run: worker attempt spans,
    #     per-round vamana spans, the kill instant, the backoff window and
    #     the resume all land on one Perfetto timeline
    #     (examples/build_spot_index.py runs the full workflow; the
    #     calibrated runtime model + policy/price comparison lives in
    #     benchmarks/bench_fleet.py -> BENCH_fleet.json).
    import pathlib
    import tempfile

    from repro.core.scheduler import RuntimeModel
    from repro.fleet import PreemptionInjector, build_scalegann_fleet
    from repro.telemetry import Tracer, check_fleet_trace

    sub = ds.data[:2000]
    fcfg = IndexConfig(n_clusters=4, degree=16, build_degree=32,
                       block_size=1024)
    tracer = Tracer(process="quickstart")
    fleet = build_scalegann_fleet(
        sub, fcfg, n_workers=2,
        injector=PreemptionInjector(kill_shard_at={0: 2}),
        runtime_model=RuntimeModel(seconds_per_vector=1e-4),  # skip
        tracer=tracer,         # calibration here; bench_fleet.py fits it
    )
    rep = fleet.report
    plain = build_scalegann(sub, fcfg, algo="vamana")
    same = all(np.array_equal(a, b) for a, b in
               zip(fleet.build.shard_graphs, plain.shard_graphs))
    print(f"[fleet] {rep.n_preemptions} preemption -> {rep.n_resumes} "
          f"resume, {rep.rounds_lost} of {rep.rounds_completed} rounds "
          f"lost, graphs identical to uninterrupted build: {same}  "
          f"(${rep.cost.total:.4f} at spot prices)")
    trace_path = pathlib.Path(tempfile.gettempdir()) / \
        "quickstart_fleet_trace.json"
    tracer.write(trace_path)
    chk = check_fleet_trace(tracer.to_chrome())
    rounds = rep.metrics["fleet_rounds_total"]["series"][0]["value"]
    print(f"[trace]  {chk['n_attempt_spans']} attempt spans / "
          f"{rounds:.0f} round spans across {chk['n_worker_tracks']} "
          f"worker tracks; kill->backoff->resume on the timeline: "
          f"{chk['ok']} — open {trace_path} at https://ui.perfetto.dev")

    # 11. The live mutable index: insert_batch runs one batched Vamana
    #     round per touched shard, delete_batch tombstones ids (masked out
    #     of every result until consolidate() makes them physical), and
    #     snapshot() is a copy-on-write generation — untouched shards
    #     share arrays with the previous snapshot, so per-shard device
    #     caches stay warm.  AnnServer.swap_topology() flips a serving
    #     process to the new generation atomically, mid-traffic.
    from repro.live import LiveConfig, LiveIndex

    li = LiveIndex.from_build(res, ds.data, cfg, LiveConfig(backend="jax"))
    rng = np.random.default_rng(7)             # fresh points: jittered
    fresh = (ds.data[rng.choice(len(ds.data), 32, replace=False)]
             + rng.normal(0, 0.05, (32, 64)).astype(np.float32))
    new_ids = li.insert_batch(fresh)           # routed to nearest shards
    victim = int(ds.gt[0, 0])                  # query 0's true top-1 ...
    li.delete_batch(np.array([victim]))        # ... tombstoned
    ids, _ = search(li.snapshot(), ds.queries, k=10, backend="jax",
                    width=96)
    found = int(np.isin(new_ids, search(
        li.snapshot(), fresh[:8], k=1, backend="jax", width=96,
    )[0].ravel()).sum())
    print(f"[live] gen {li.generation}: inserted {len(new_ids)} "
          f"(first 8 self-findable: {found}/8), deleted id {victim} "
          f"returned anywhere: {bool((ids == victim).any())}")
    rep = li.consolidate()                     # dead rows go physical
    print(f"[live] consolidate: re-pruned {rep['rows_repruned']} rows, "
          f"removed {rep['removed']} tombstones "
          f"({li.n_live} live of {li.n_vectors} ids)")

    async def swap_mid_traffic():
        sc = ServingConfig(backend="jax", k=10, width=96, max_batch=32,
                           max_wait_ms=2.0)
        async with AnnServer(li.snapshot(), config=sc) as srv:
            wave = [srv.submit_nowait(q) for q in ds.queries[:16]]
            li.insert_batch(fresh[:16] + 0.1)
            gen = srv.swap_topology(li.snapshot())  # atomic epoch swap
            outs = await asyncio.gather(*wave)
            print(f"[live] epoch swap -> serving generation {gen}, "
                  f"{len(outs)}/16 in-flight futures resolved")

    asyncio.run(swap_mid_traffic())

    # 12. Crash-consistent durability: from the first save() on, every
    #     mutation appends a CRC32-framed WAL record *before* touching
    #     memory, and save() commits checksummed snapshot generations
    #     atomically (segments -> manifest -> CURRENT flip).  Kill the
    #     process at any byte boundary -- load() restores the committed
    #     generation, truncates a torn final WAL record, replays the
    #     tail, and the recovered index serves ids identical to one
    #     that never crashed (bench_durability.py CI-guards this across
    #     backend x dtype; the crash-point table is in README §12).
    from repro.durability import CrashInjector, SimulatedCrash

    idx_dir = pathlib.Path(tempfile.mkdtemp(prefix="quickstart_idx_"))
    li.save(idx_dir)                       # snapshot + arms the WAL
    li.close()                             # detach: li continues purely
                                           # in memory as the uncrashed
                                           # reference for the disk copy
    ops = [("insert", fresh + 0.2), ("delete", new_ids[:4])]
    for op, arg in ops:                    # the uncrashed reference run
        (li.insert_batch if op == "insert" else li.delete_batch)(arg)
    ref_ids, _ = search(li.snapshot(), ds.queries, k=10, backend="jax",
                        width=96)

    # same mutations against the on-disk copy, with a kill injected
    # mid-append on the second record (a torn half-frame lands on disk):
    rec = LiveIndex.load(idx_dir, cfg, LiveConfig(backend="jax"),
                         injector=CrashInjector(
                             crash_at={"wal.append.torn": 2}))
    seq0, pos = rec.wal_seq, 0
    while pos < len(ops):
        op, arg = ops[pos]
        try:
            (rec.insert_batch if op == "insert" else
             rec.delete_batch)(arg)
            pos += 1
        except SimulatedCrash as c:        # the "kill -9"
            print(f"[durability] crashed at {c.point}; recovering")
            rec = LiveIndex.load(idx_dir, cfg, LiveConfig(backend="jax"))
            pos = rec.wal_seq - seq0       # replayed ops aren't re-run

    async def serve_recovered():
        sc = ServingConfig(backend="jax", k=10, width=96, max_batch=32,
                           max_wait_ms=2.0)
        async with AnnServer(li.snapshot(), config=sc) as srv:
            srv.swap_topology(rec.snapshot(), reason="recovery")
            outs = await asyncio.gather(
                *(srv.submit(q) for q in ds.queries)
            )
        served = np.stack([o.ids for o in outs])
        print(f"[durability] recovered from kill: served ids identical "
              f"to the uncrashed run: "
              f"{bool(np.array_equal(served, ref_ids))}")

    asyncio.run(serve_recovered())
    rec.close()


if __name__ == "__main__":
    enable_compile_cache()
    main()
