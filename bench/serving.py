"""What the serving drivers share: the index they serve, the server, its
event loop, and the bookkeeping of requests and answers.

Set-up builds the index from the deployment's data (``build_scalegann``),
or loads the one an earlier run of the same program built from the same
configuration (:func:`built_index`); wraps it in the topology the cell names
(``routed``: the live index's snapshot of the replicated shards;
``merged``: the merged graph); starts an ``AnnServer`` and waits for its own
warm-up; for the routed topology compiles every shape ``run_split`` can meet
(:func:`warm_routed`); then runs the driver's warm phase on a separate
stream of the same traffic.  The window's per-request records feed the
end-to-end metrics and the judged answers.
"""

from __future__ import annotations

import asyncio
import dataclasses
import hashlib
import json
import os
import pickle

import numpy as np

from bench import common

WINDOW_STREAM = 1
WARM_STREAM = 2
TRACE_STREAM = 3
DRAIN_S = 60.0  # how long past the window's close an answer is waited for
INDEX_CACHE = common.ROOT / ".bench_cache"


def _program_digest() -> str:
    """A digest of the program's sources and the JAX it runs on."""
    import jax

    h = hashlib.sha256(jax.__version__.encode())
    src = common.ROOT / "src"
    for path in sorted(src.rglob("*.py")):
        h.update(str(path.relative_to(src)).encode())
        h.update(path.read_bytes())
    return h.hexdigest()


def built_index(data: np.ndarray, config: dict, *, cache: bool):
    """``build_scalegann`` of the deployment's data.

    The base vectors are fixed by the configuration, so every run of a
    serving cell would build the same index.  With ``cache`` the result is
    kept under ``.bench_cache/`` in the checkout, keyed by the program's
    sources and the configuration, and the next run loads it: a change to
    the program builds afresh in its first run.
    """
    from repro.configs.base import IndexConfig
    from repro.core.builder import build_scalegann

    key = hashlib.sha256(json.dumps(
        {"program": _program_digest(), "config": config},
        sort_keys=True).encode()).hexdigest()[:32]
    path = INDEX_CACHE / f"index-{key}.pkl"
    if cache and path.exists():
        with open(path, "rb") as f:
            return pickle.load(f)
    res = build_scalegann(data, IndexConfig(**config["index"]), algo="cagra",
                          max_retries=0)
    if cache:
        INDEX_CACHE.mkdir(exist_ok=True)
        tmp = path.with_suffix(".tmp")
        with open(tmp, "wb") as f:
            pickle.dump(res, f, protocol=pickle.HIGHEST_PROTOCOL)
        os.replace(tmp, path)
    return res


def group_sizes(max_batch: int) -> list[int]:
    """Every query-group size ``run_split`` can hand one shard's beam.

    The server pads each engine call to ``bucket_batch_size``; within a call
    of ``nq`` queries, a shard probed by ``m < nq`` of them gets
    ``min(_bucket_size(m), nq)`` rows and one probed by all gets ``nq``.
    Both paddings are the program's own functions, so a program that changes
    its buckets changes this list with it.
    """
    from repro.search.types import _bucket_size
    from repro.serving.server import bucket_batch_size

    sizes = set()
    for nq in {bucket_batch_size(m, max_batch)
               for m in range(1, max_batch + 1)}:
        sizes.add(nq)
        sizes.update(min(_bucket_size(m), nq) for m in range(1, nq))
    return sorted(sizes)


def stream(seed: int, which: int) -> np.random.Generator:
    return np.random.default_rng([seed % (1 << 64), which])


@dataclasses.dataclass
class Record:
    """Requests of one phase, indexed by request."""

    rows: np.ndarray  # [R] pool row of each request
    t_due: np.ndarray  # [R] scheduled (open loop) or sent (closed) time
    t_done: np.ndarray  # [R] resolution time, inf when never answered
    ids: list  # [R] answer ids or None


class Serving:
    def __init__(self, ctx):
        self.ctx = ctx
        self.params = ctx.params
        self.loop = asyncio.new_event_loop()
        self.srv = None

    def run(self, coro):
        return self.loop.run_until_complete(coro)

    def start(self) -> None:
        from repro.configs.base import IndexConfig
        from repro.live import LiveIndex
        from repro.serving.server import AnnServer, ServingConfig

        data = self.ctx.dep.data
        icfg = IndexConfig(**self.ctx.config["index"])
        res = built_index(data, self.ctx.config, cache=self.ctx.persistent)
        topo = self.params["topology"]
        if topo == "routed":
            target, tdata = LiveIndex.from_build(res, data, icfg).snapshot(), None
        elif topo == "merged":
            target, tdata = res.index, data
        else:
            raise ValueError(f"unknown topology {topo!r}")
        # served under the configuration's metric unless the cell says
        scfg = ServingConfig(**{"k": self.ctx.k,
                                "metric": self.ctx.config["metric"],
                                **self.params["server"]})

        async def go():
            self.srv = AnnServer(target, scfg, data=tdata)
            self.srv.start()
            # answered only after the server's own warm-up of its shapes
            await self.srv.submit(self.ctx.pool_f32[0])

        self.run(go())
        if topo == "routed":
            self.warm_routed(target, scfg)

    def warm_routed(self, topo, scfg) -> None:
        """Compile every shape the routed driver can meet, through the
        program's own functions.  Which shapes a window meets depends on how
        its batches route, so traffic alone leaves some to compile inside
        the window:

        - the beam, specialised to a shard's rows and its query group's
          size: each shard at every size of :func:`group_sizes`, searched
          on a view of that one shard;
        - the exact routing tile of the queries whose quantized routing
          is ambiguous (uint8), one shape per count of such queries.
        """
        from repro.search import ShardTopology, search
        from repro.search.types import _query_centroid_distances

        q = self.ctx.pool_f32
        sizes = group_sizes(scfg.max_batch)
        for s, ids in enumerate(topo.shard_ids):
            one = ShardTopology(
                data=topo.data, shard_ids=[ids],
                shard_graphs=[topo.shard_graphs[s]], metric=scfg.metric,
                centroids=topo.centroids[s:s + 1])
            for b in sizes:
                search(one, np.resize(q, (b, q.shape[1])), scfg.k,
                       backend=scfg.backend, width=scfg.width,
                       n_entries=scfg.n_entries, nprobe=scfg.nprobe,
                       dtype=scfg.dtype, rerank=scfg.rerank)
        cent = np.asarray(topo.centroids, np.float32)
        for n in range(1, scfg.max_batch + 1):
            _query_centroid_distances(np.resize(q, (n, q.shape[1])), cent,
                                      scfg.metric)

    def traced(self, prof, phase) -> int | None:
        """With the profiler on, run ``phase()`` (a further stretch of the
        same traffic, after the window) under it and return the engine
        calls it made; the window itself runs untraced, so starting and
        stopping the profiler stalls none of its requests."""
        if not prof.active:
            return None
        n0 = self.srv.stats.n_batches
        prof.start()
        self.run(phase())
        prof.stop()
        return self.srv.stats.n_batches - n0

    def reset_stats(self) -> None:
        from repro.serving.stats import ServerStats

        self.srv.stats = ServerStats()

    async def drain(self, futs: list, t_close: float) -> None:
        pending = [f for f in futs if f is not None and not f.done()]
        if pending:
            await asyncio.wait(pending, timeout=max(
                0.0, t_close + DRAIN_S - self.srv.clock()))

    def close(self) -> None:
        if self.srv is not None:
            self.run(self.srv.stop())
            self.srv = None
        self.run(self.loop.shutdown_default_executor())  # the engine threads
        self.loop.close()


def collect(futs: list, t_due: np.ndarray) -> tuple[np.ndarray, list, int]:
    """``(t_done, ids, failed)`` of each request's future (``None`` for a
    request that was refused at submission)."""
    t_done = np.full(len(futs), np.inf)
    ids: list = [None] * len(futs)
    failed = 0
    for j, f in enumerate(futs):
        if f is None or not f.done() or f.cancelled() or f.exception():
            failed += f is not None and f.done()
            continue
        r = f.result()
        t_done[j] = t_due[j] + r.latency_s
        ids[j] = r.ids
    return t_done, ids, failed


def answers(rec: Record, k: int):
    """``(rows, ids, n_unanswered)`` for the judged comparison."""
    got = [j for j, a in enumerate(rec.ids) if a is not None]
    ids = (np.stack([np.asarray(rec.ids[j], np.int64) for j in got])
           if got else np.empty((0, k), np.int64))
    return rec.rows[got], ids, len(rec.ids) - len(got)
