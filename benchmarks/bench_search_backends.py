"""Search-backend benchmark: QPS + distance computations per query.

Runs every registered backend over the 2k-vector synthetic fixture on both
query topologies (merged ScaleGANN index, split-only shards), the
centroid-routed split path (``nprobe`` ∈ {1, 2, all} over the ScaleGANN
partition's replicated shards), and the staged-dtype sweep
(f32/bf16/uint8 × scatter/routed on the ``jax`` serving backend, with
bytes-per-distance accounting), and writes ``BENCH_search.json`` next to
the repo root so future PRs have a perf trajectory for the serving path.
Jitted backends are warmed on the exact query shape first, so QPS measures
steady-state serving, not tracing.

    PYTHONPATH=src python benchmarks/bench_search_backends.py
    PYTHONPATH=src python benchmarks/bench_search_backends.py --smoke

``--smoke`` is the CI profile: one repeat, fewer queries — cheap enough to
run *after* the test suite finishes (never concurrently with it: this
box's suite saturates the machine and silently distorts QPS numbers), with
every claim still computed and guarded.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import pathlib
import time

from repro.common.compile_cache import enable_compile_cache
from repro.configs.base import IndexConfig
from repro.core import builder
from repro.data.synthetic import make_clustered, recall_at
from repro.search import available_backends, search
from repro.telemetry import (NULL_TRACER, Tracer, set_tracer,
                             validate_chrome_trace)

N_VECTORS = 2000
N_QUERIES = 256
WIDTH = 64
K = 10
REPEATS = 3
# Routing needs enough shards to prune: 2k vectors over 8 replicated
# ScaleGANN shards (the merged/split sections keep their historical
# 4-cluster fixture for trajectory comparability).
N_SHARDS_ROUTED = 8

# storage bytes per element for each distance stage
DTYPE_ITEMSIZE = {"f32": 4, "bf16": 2, "uint8": 1}

OUT_PATH = pathlib.Path(__file__).resolve().parent.parent / "BENCH_search.json"


def _bench_one(topo, ds, backend: str, *, nprobe=None, dtype: str = "f32",
               repeats: int = REPEATS) -> dict:
    dim = ds.queries.shape[1]
    kw = {"backend": backend, "width": WIDTH}
    if nprobe is not None:
        kw["nprobe"] = nprobe
    if dtype != "f32":
        kw["dtype"] = dtype
    search(topo, ds.queries, K, **kw)  # warm (jit trace + routing shapes)
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        ids, st = search(topo, ds.queries, K, **kw)
        best = min(best, time.perf_counter() - t0)
    n_total = st.n_distance_computations
    n_quant = st.n_quantized_distance_computations
    n_rerank = st.n_rerank_distance_computations
    # memory traffic per scored pair: quantized scores stream the staged
    # storage dtype, everything else (routing tile, re-rank, f32 beams)
    # streams f32 rows
    bytes_total = dim * (DTYPE_ITEMSIZE[dtype] * n_quant
                         + 4 * (n_total - n_quant))
    return {
        "qps": len(ds.queries) / best,
        "latency_s_per_batch": best,
        "recall_at_10": recall_at(ids, ds.gt, K),
        "mean_distance_computations_per_query":
            n_total / len(ds.queries),
        "mean_hops_per_query": st.n_hops / len(ds.queries),
        "mean_quantized_distance_computations_per_query":
            n_quant / len(ds.queries),
        "mean_rerank_distance_computations_per_query":
            n_rerank / len(ds.queries),
        "bytes_per_distance": bytes_total / max(n_total, 1),
    }


def bench_topology(topo_name: str, topo, ds, repeats: int) -> dict:
    out = {}
    for backend in available_backends():
        out[backend] = row = _bench_one(topo, ds, backend, repeats=repeats)
        print(f"{topo_name:16s} {backend:7s} qps={row['qps']:8.0f} "
              f"recall@10={row['recall_at_10']:.3f} "
              f"ndist/q={row['mean_distance_computations_per_query']:.0f}")
    return out


def bench_routed(topo, ds, n_shards: int, repeats: int) -> dict:
    """Routed split path: nprobe ∈ {1, 2, all} per backend, so the routing
    win (ndist/q, QPS) and its recall cost land in BENCH_search.json."""
    out = {}
    for nprobe in (1, 2, n_shards):
        label = "nprobe=all" if nprobe == n_shards else f"nprobe={nprobe}"
        out[label] = {}
        for backend in available_backends():
            out[label][backend] = row = _bench_one(
                topo, ds, backend, nprobe=nprobe, repeats=repeats
            )
            print(f"routed {label:11s} {backend:7s} qps={row['qps']:8.0f} "
                  f"recall@10={row['recall_at_10']:.3f} "
                  f"ndist/q="
                  f"{row['mean_distance_computations_per_query']:.0f}")
    return out


def bench_dtypes(topo, ds, dtypes: list[str], repeats: int) -> dict:
    """Staged-dtype sweep on the serving (`jax`) backend: every requested
    dtype × {scatter, routed nprobe=2}, with bytes-per-distance — the
    memory-traffic proxy the uint8 acceptance claim guards."""
    out = {}
    for path, nprobe in (("scatter", None), ("routed_nprobe2", 2)):
        out[path] = {}
        for dtype in dtypes:
            out[path][dtype] = row = _bench_one(
                topo, ds, "jax", nprobe=nprobe, dtype=dtype,
                repeats=repeats,
            )
            print(f"dtype {path:14s} {dtype:5s} qps={row['qps']:8.0f} "
                  f"recall@10={row['recall_at_10']:.3f} "
                  f"ndist/q="
                  f"{row['mean_distance_computations_per_query']:.0f} "
                  f"B/dist={row['bytes_per_distance']:.2f}")
    return out


def main(argv=None) -> dict:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="CI profile: 1 repeat, 128 queries; run it only "
                         "on an otherwise-idle machine (after the test "
                         "suite, never alongside it)")
    ap.add_argument("--dtypes", default="f32,bf16,uint8",
                    help="comma-separated stage list for the dtype sweep")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="write a Chrome/Perfetto trace (search.engine "
                         "spans per backend call, plus build phases for "
                         "the fixture indexes)")
    args = ap.parse_args(argv)
    tracer = None
    if args.trace_out:
        tracer = Tracer(process="bench_search_backends")
        set_tracer(tracer)
    repeats = 1 if args.smoke else REPEATS
    n_queries = 128 if args.smoke else N_QUERIES
    dtypes = [d for d in args.dtypes.split(",") if d]

    ds = make_clustered(N_VECTORS, 32, n_queries=n_queries, spread=1.0,
                        seed=7)
    cfg = IndexConfig(n_clusters=4, degree=16, build_degree=32,
                      block_size=512)
    merged = builder.build_scalegann(ds.data, cfg, n_workers=2)
    split = builder.build_extended_cagra(ds.data, cfg, n_workers=2)
    routed = builder.build_scalegann(
        ds.data, dataclasses.replace(cfg, n_clusters=N_SHARDS_ROUTED),
        n_workers=2,
    )
    routed_topo = routed.shard_topology(ds.data)

    results = {
        "fixture": {"n_vectors": N_VECTORS, "n_queries": n_queries,
                    "dim": 32, "width": WIDTH, "k": K,
                    "smoke": bool(args.smoke)},
        "merged": bench_topology("merged", merged.topology(ds.data), ds,
                                 repeats),
        "split": bench_topology("split", split.topology(ds.data), ds,
                                repeats),
        "split_routed_fixture": {
            "n_shards": N_SHARDS_ROUTED,
            "builder": "scalegann (selective replication, pre-merge shards)",
            "replica_proportion": routed.stats["replica_proportion"],
        },
        "split_routed": bench_routed(routed_topo, ds, N_SHARDS_ROUTED,
                                     repeats),
        "dtype_sweep": bench_dtypes(routed_topo, ds, dtypes, repeats),
    }
    speedup = (results["merged"]["jax"]["qps"]
               / results["merged"]["numpy"]["qps"])
    results["jax_over_numpy_qps"] = speedup
    print(f"jax/numpy merged QPS: {speedup:.2f}x")

    # the routing claim (ISSUE 2 acceptance): nprobe=2 cuts ndist/q >= 2x
    # versus full scatter on the same shards, at recall@10 >= 0.95
    full = results["split_routed"]["nprobe=all"]["jax"]
    np2 = results["split_routed"]["nprobe=2"]["jax"]
    cut = (full["mean_distance_computations_per_query"]
           / np2["mean_distance_computations_per_query"])
    results["routed_nprobe2_distance_cut"] = cut
    results["claim.routed_nprobe2_cut_ge_2x_at_recall_095"] = bool(
        cut >= 2.0 and np2["recall_at_10"] >= 0.95
    )
    print(f"routed nprobe=2 distance cut: {cut:.2f}x "
          f"(recall@10 {np2['recall_at_10']:.3f})")

    # the quantization claim (ISSUE 4 acceptance): the uint8 stage cuts
    # bytes-per-distance >= 3x vs f32 while holding recall@10 within 0.01,
    # on both the scatter and the routed nprobe=2 path
    if "uint8" in dtypes and "f32" in dtypes:
        sweeps = results["dtype_sweep"]
        cuts = {}
        ok = True
        for path in ("scatter", "routed_nprobe2"):
            f32 = sweeps[path]["f32"]
            u8 = sweeps[path]["uint8"]
            cuts[path] = f32["bytes_per_distance"] / u8["bytes_per_distance"]
            ok = ok and (cuts[path] >= 3.0) and (
                u8["recall_at_10"] >= f32["recall_at_10"] - 0.01)
        results["uint8_bytes_per_distance_cut"] = cuts
        results["claim.uint8_bytes_cut_ge_3x_at_recall_within_001"] = ok
        print("uint8 bytes/distance cut: "
              + ", ".join(f"{p} {c:.2f}x" for p, c in cuts.items())
              + f" (claim {'holds' if ok else 'FAILS'})")

    if tracer is not None:
        set_tracer(NULL_TRACER)
        n_schema = len(validate_chrome_trace(tracer.to_chrome()))
        tracer.write(args.trace_out)
        results["trace"] = {"path": str(args.trace_out),
                            "schema_errors": n_schema}
        print(f"trace: {args.trace_out} (schema errors {n_schema})")

    OUT_PATH.write_text(json.dumps(results, indent=2, sort_keys=True))
    print(f"wrote {OUT_PATH}")
    return results


if __name__ == "__main__":
    enable_compile_cache()
    main()
