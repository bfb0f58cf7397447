"""Smoke run of the build-and-serve path on one TPU chip at SIFT1M scale.

    python3 chip_smoke.py [--n 1000000] [--seed 0]

Phases, in one process on one chip:

  device  — the first JAX device is a TPU and the Pallas dispatch policy is
            ``auto`` (so ``REPRO_PALLAS`` cannot hide the kernels);
  data    — a SIFT1M-shaped clustered set (N × 128 f32, 1000 queries, L2)
            from ``--seed``; exact ground truth through the Pallas ``knn``
            kernel, checked against the plain jnp reference on 64 queries;
  build   — ``build_scalegann`` with the default ``IndexConfig`` (16
            clusters, R = 64, L = 128, ε = 1.2): partition, CAGRA shard
            builds, merge; every shard must build on its first attempt;
  serve   — the merged graph through ``search(backend="jax")``, and the
            routed live snapshot through ``AnnServer`` with single-query
            submits, half f32 and half uint8; no failed or rejected
            requests, no engine-call signature first seen after the
            server's warm-up, recall@10 >= 0.80 on every path; the XLA
            compiles made while the traffic is served are counted;
  fused   — the Pallas ``fused_beam`` on a CAGRA graph over the first
            ``FUSED_N`` rows, bit-identical (ids, n_dist, hops) to its XLA
            lowering for f32, bf16 and uint8 with the fused re-rank.

Every phase prints its lines first; the last line of standard output is
the JSON result.  Any failure exits non-zero before that line.  Timings
printed here are from a single smoke run, not a measurement.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
RECALL_FLOOR = 0.80
SIFT_D = 128
SIFT_QUERIES = 1000
GT_K = 10
REF_QUERIES = 64
# beam width of the merged and the routed search, and the merged search's
# seeds.  The clustered set's 24 Gaussians sit far apart, so a graph has no
# edges between them and a query reaches its cluster only from a seed in
# it; and inside one 128-d isotropic Gaussian a width-64 beam stops short.
# At N = 1M on v5e, merged recall@10 was 0.57 at width 64 with 17 seeds and
# 0.85 at width 256 with 256.  Routed f32 at width 256 was 0.74 seeded from
# each probed shard's centroid entry alone and 0.90 with the server's
# default 16 stratified seeds besides it (capacity spills parts of a
# Gaussian into shards centred on others).
SEARCH_WIDTH = 256
SERVE_QUERIES = 256
# largest shard the Pallas fused_beam holds in VMEM on v5e at D = 128,
# R = 64 for all three stages (uint8 with re-rank binds; see
# kernels.beam.VMEM_LIMIT_BYTES)
FUSED_N = 86015
# few enough that the XLA lowering scores from its precomputed query×shard
# tile (PRECOMPUTE_TILE_LIMIT), the same matmul the Pallas wrapper runs
FUSED_QUERIES = 32


def log(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


def fail(msg: str) -> None:
    print(f"[smoke] FAILED: {msg}", file=sys.stderr, flush=True)
    sys.exit(1)


def check_device():
    import jax

    from repro.common.compile_cache import enable_compile_cache
    from repro.kernels import ops

    cache_dir = enable_compile_cache()
    devs = jax.devices()
    d0 = devs[0]
    log(f"device platform={d0.platform} kind={d0.device_kind} "
        f"count={len(devs)} compile_cache={cache_dir}")
    if d0.platform != "tpu":
        fail(f"no TPU: JAX reports platform {d0.platform!r}")
    if ops.pallas_mode() != "auto":
        fail(f"Pallas dispatch is {ops.pallas_mode()!r}; the smoke needs "
             "'auto' so the kernels run on the chip")
    return {"platform": d0.platform, "kind": d0.device_kind,
            "count": len(devs)}


def count_compiles() -> dict:
    """Live counts of persistent compile-cache hits and misses, and of XLA
    executables compiled or loaded from that cache (``backend_compiles``:
    one per new jit signature, eager op shapes included)."""
    from jax import monitoring

    counts = {"cache_hits": 0, "cache_misses": 0, "backend_compiles": 0}

    def on_event(event: str, **_):
        key = event.rsplit("/", 1)[-1]
        if key in counts:
            counts[key] += 1

    def on_duration(event: str, _secs: float, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            counts["backend_compiles"] += 1

    monitoring.register_event_listener(on_event)
    monitoring.register_event_duration_secs_listener(on_duration)
    return counts


def ids_match_with_ties(got, want, data, queries, tol=1e-4):
    """Per query, equal id sets, or sets that differ only by ids whose exact
    (f64) distance ties the k-th nearest within ``tol`` (relative).
    Returns the number of queries that differ only by such ties."""
    import numpy as np

    tied = 0
    for qi, (g, w) in enumerate(zip(got, want)):
        if set(g.tolist()) == set(w.tolist()):
            continue
        union = np.union1d(g, w)
        diff = np.setxor1d(g, w)
        q = queries[qi].astype(np.float64)
        d = ((data[union].astype(np.float64) - q) ** 2).sum(axis=1)
        kth = np.sort(d)[len(g) - 1]
        dd = ((data[diff].astype(np.float64) - q) ** 2).sum(axis=1)
        if np.any(np.abs(dd - kth) > tol * max(kth, 1e-12)):
            fail(f"query {qi}: Pallas knn ids {sorted(g.tolist())} != "
                 f"reference {sorted(w.tolist())} beyond a tie")
        tied += 1
    return tied


def phase_data(n: int, seed: int):
    import jax.numpy as jnp
    import numpy as np

    from repro.data.synthetic import make_clustered
    from repro.kernels import ref

    t0 = time.perf_counter()
    ds = make_clustered(n, SIFT_D, n_queries=SIFT_QUERIES, gt_k=GT_K,
                        seed=seed, name=f"sift-shaped-{n}")
    t_data = time.perf_counter() - t0
    log(f"data: {ds.data.shape} {ds.data.dtype}, {len(ds.queries)} queries, "
        f"ground truth via Pallas knn; data+gt {t_data:.2f}s")
    x = jnp.asarray(ds.data)
    q = jnp.asarray(ds.queries[:REF_QUERIES])
    _, ref_ids = ref.knn(q, x, GT_K, "l2")
    tied = ids_match_with_ties(ds.gt[:REF_QUERIES],
                               np.asarray(ref_ids, np.int64),
                               ds.data, ds.queries)
    log(f"data: Pallas knn ground truth == jnp reference on {REF_QUERIES} "
        f"queries ({tied} differ only by distance ties)")
    return ds


def recall(found, gt) -> float:
    from repro.data.synthetic import recall_at

    return recall_at(found, gt, GT_K)


def phase_build(ds):
    from repro.configs.base import IndexConfig
    from repro.core.builder import build_scalegann

    cfg = IndexConfig()
    res = build_scalegann(ds.data, cfg, algo="cagra", max_retries=0)
    if any(a != 1 for a in res.shard_attempts):
        fail(f"shard attempts {res.shard_attempts}: a shard was retried")
    sizes = [len(s.ids) for s in res.shards]
    log(f"build: {len(sizes)} shards, sizes {min(sizes)}..{max(sizes)}, "
        f"replica proportion {res.stats['replica_proportion']:.4f}, "
        f"all built on attempt 1")
    log(f"build: partition {res.partition_s:.2f}s, shard builds "
        f"{res.wall_build_s:.2f}s, merge {res.merge_s:.2f}s "
        "(one smoke run, not a benchmark)")
    return cfg, res


def phase_serve(ds, cfg, res, compiles: dict):
    import numpy as np

    from repro.live import LiveIndex
    from repro.search import search
    from repro.serving.server import AnnServer, ServingConfig

    t0 = time.perf_counter()
    chunk = 250  # the jax backend's visited tags are [Q, N] int32
    ids = np.concatenate([
        search(res.index, ds.queries[s:s + chunk], GT_K, backend="jax",
               data=ds.data, width=SEARCH_WIDTH, n_entries=SEARCH_WIDTH)[0]
        for s in range(0, len(ds.queries), chunk)
    ])
    r_merged = recall(ids, ds.gt)
    log(f"serve: merged jax recall@10 {r_merged:.4f} over "
        f"{len(ds.queries)} queries (width {SEARCH_WIDTH}, "
        f"{SEARCH_WIDTH} seeds), {time.perf_counter() - t0:.2f}s")
    if r_merged < RECALL_FLOOR:
        fail(f"merged recall@10 {r_merged:.4f} < {RECALL_FLOOR}")

    snap = LiveIndex.from_build(res, ds.data, cfg).snapshot()
    scfg = ServingConfig(backend="jax", nprobe="auto", width=SEARCH_WIDTH,
                         pretrace_dtypes=("uint8",))
    dtypes = ["f32" if i % 2 == 0 else "uint8" for i in range(SERVE_QUERIES)]
    queries = ds.queries[:SERVE_QUERIES]

    async def drive():
        async with AnnServer(snap, scfg) as srv:
            # the server answers this only after its warm-up; it repeats the
            # warm-up's batch-of-1 f32 call (index row 0), so it compiles
            # nothing and marks where the counted traffic starts
            await srv.submit(snap.data[0])
            before = compiles["backend_compiles"]
            out = await asyncio.gather(*[
                srv.submit(q, dtype=dt) for q, dt in zip(queries, dtypes)
            ])
            during = compiles["backend_compiles"] - before
        return srv, out, during

    t0 = time.perf_counter()
    srv, out, during = asyncio.run(drive())
    st = srv.stats
    post_warm = srv.stats.registry.counter(
        "serving_post_warm_signatures_total").value
    log(f"serve: routed AnnServer (width {scfg.width}, centroid entry + "
        f"{scfg.n_entries} seeds per probed shard) {st.n_completed} "
        f"completed, {st.n_failed} failed, {st.n_rejected} rejected, "
        f"{int(post_warm)} post-warm signatures, {st.n_batches} batches, "
        f"{time.perf_counter() - t0:.2f}s including warm-up")
    log(f"serve: {during} XLA compiles while the {SERVE_QUERIES} requests "
        "were served (the routed driver's per-shard query-group shapes are "
        "not pre-traced)")
    if st.n_failed or st.n_rejected or st.n_completed != SERVE_QUERIES + 1:
        fail("routed serving dropped requests")
    if post_warm:
        fail(f"{int(post_warm)} engine signatures compiled after warm-up")
    got = np.stack([r.ids for r in out])
    for dt in ("f32", "uint8"):
        sel = np.array([d == dt for d in dtypes])
        r = recall(got[sel], ds.gt[:SERVE_QUERIES][sel])
        log(f"serve: routed {dt} recall@10 {r:.4f} over {sel.sum()} queries")
        if r < RECALL_FLOOR:
            fail(f"routed {dt} recall@10 {r:.4f} < {RECALL_FLOOR}")


def phase_fused(ds, cfg):
    import jax.numpy as jnp
    import numpy as np

    from repro.core.cagra import build_shard_index
    from repro.kernels import beam
    from repro.search.types import QuantSpec

    need = beam.pallas_vmem_bytes(FUSED_N, SIFT_D, cfg.degree, jnp.uint8,
                                  dx=SIFT_D)
    log(f"fused: N={FUSED_N}, modelled VMEM (uint8 + re-rank) {need} bytes, "
        f"bound {beam.VMEM_LIMIT_BYTES}")
    data = np.ascontiguousarray(ds.data[:FUSED_N])
    t0 = time.perf_counter()
    graph = build_shard_index(data, cfg).graph
    log(f"fused: CAGRA graph over {FUSED_N} rows in "
        f"{time.perf_counter() - t0:.2f}s")
    entries = jnp.asarray(
        np.linspace(0, FUSED_N - 1, cfg.n_clusters + 1).astype(np.int32))
    q = ds.queries[:FUSED_QUERIES]
    spec = QuantSpec.from_data(data)
    k, kq = GT_K, 4 * GT_K
    stages = {
        "f32": (jnp.asarray(data), jnp.asarray(q), {}),
        "bf16": (jnp.asarray(data, jnp.bfloat16),
                 jnp.asarray(q, jnp.bfloat16), {}),
        "uint8": (jnp.asarray(spec.quantize(data)),
                  jnp.asarray(spec.quantize(q)),
                  dict(scale=spec.scale, zp=spec.zero_point,
                       x_exact=data, q_exact=q, rerank_k=k)),
    }
    g = jnp.asarray(graph)
    for name, (x, qx, kw) in stages.items():
        outs = {}
        for lowering in ("pallas", "xla"):
            t0 = time.perf_counter()
            o = beam.fused_beam(x, g, entries, qx, kq if kw else k,
                                width=64, lowering=lowering, **kw)
            outs[lowering] = [np.asarray(a) for a in o]
            log(f"fused: {name} {lowering} {time.perf_counter() - t0:.2f}s "
                "including compile")
        (pi, _, pn, ph, pr), (xi, _, xn, xh, xr) = outs["pallas"], outs["xla"]
        for what, a, b in (("ids", pi, xi), ("n_dist", pn, xn),
                           ("hops", ph, xh), ("n_rerank", pr, xr)):
            if not np.array_equal(a, b):
                bad = int(np.sum(np.any(a != b, axis=-1) if a.ndim > 1
                                 else a != b))
                fail(f"fused {name}: Pallas {what} differ from the XLA "
                     f"lowering on {bad} of {len(q)} queries")
        log(f"fused: {name} Pallas == XLA lowering on ids, n_dist, hops "
            f"({int(pn.sum())} distances, {int(ph.sum())} hops)")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--n", type=int, default=1_000_000,
                    help="base vectors (SIFT1M: 1,000,000)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    sys.path.insert(0, os.path.join(HERE, "src"))
    try:
        import repro  # noqa: F401
    except ImportError:
        fail(f"the repro package is not under {HERE}/src")

    t_all = time.perf_counter()
    device = check_device()
    compiles = count_compiles()
    ds = phase_data(args.n, args.seed)
    cfg, res = phase_build(ds)
    phase_serve(ds, cfg, res, compiles)
    phase_fused(ds, cfg)
    log(f"compile cache: {compiles['cache_hits']} hits, "
        f"{compiles['cache_misses']} misses; "
        f"{compiles['backend_compiles']} XLA compiles in all")
    log(f"total {time.perf_counter() - t_all:.2f}s (one smoke run)")
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
