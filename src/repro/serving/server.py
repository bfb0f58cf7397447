"""`AnnServer` — the asyncio micro-batching ANN query server.

This is the missing layer between "millions of single-query users" and the
engine's batch sweet spot (BENCH_search.json: the ``jax`` backend is ~4×
the numpy reference's QPS at batch 256, and roughly *none* of that shows up
at batch 1).  The BANG/PilotANN lesson is that sustained ANN throughput is
a *feeding* problem — keep the accelerator's batch lanes dense — and
feeding is a front-end concern:

  submit() ──► RequestQueue (bounded admission) ──► MicroBatcher
      ▲                                                 │ flush on
      │ future resolved                                 │ max_batch /
      │                                                 ▼ max_wait_ms
  QueryResult ◄── SearchWorker ──► repro.search.search(batch, backend=…)

One worker drains batches into the engine (off-loop in an executor thread,
so arrivals keep flowing while the engine computes), resolves each
request's future, and feeds :class:`~repro.serving.stats.ServerStats`.
Batch shapes are padded to powers of two (:func:`bucket_batch_size`) so
the jitted backends retrace O(log max_batch) times, not once per
occupancy — and those shapes are pre-traced at startup.

Not to be confused with ``repro.serve`` — the *LM decode* serving engine;
see that module's docstring for the naming split.
"""

from __future__ import annotations

import asyncio
import dataclasses
import itertools
import time
from typing import Any

import numpy as np

from repro.search import (DEFAULT_RERANK, SearchStats, as_topology,
                          get_backend, parse_dtype, parse_nprobe, search)
from repro.serving.policy import AdaptiveWindow, FixedWindow, SLOPolicy
from repro.serving.queue import (MicroBatcher, PendingRequest, RequestQueue,
                                 ServerOverloadedError)
from repro.serving.stats import ServerStats
from repro.telemetry import (SignatureGuard, collect_stages, current_tracer,
                             install_compile_listener)

# sentinel: "use the server-level default" for per-request options
USE_DEFAULT = object()

# distinguishes trace-lane ids across servers in one process (a bench runs
# several trials; request ids restart at 0 but lane keys must not collide)
_SERVER_SEQ = itertools.count()

_POST_WARM_METRIC = (
    "serving_post_warm_signatures_total",
    "engine-call signatures first seen after warm-up "
    "(mid-traffic retrace risk)",
)

_GENERATION_METRIC = (
    "serving_topology_generation",
    "current served topology generation (bumped by swap_topology)",
)


def bucket_batch_size(m: int, max_batch: int) -> int:
    """Engine-call batch shape for ``m`` real requests: the next power of
    two, capped at ``max_batch``.

    Coarser than the split driver's 8-steps-per-octave buckets on purpose:
    a server sees *every* occupancy over its lifetime, and each distinct
    shape is a fresh jit trace (~seconds) that lands in some unlucky
    request's latency.  Powers of two keep the shape set to
    ``log2(max_batch)+1`` — small enough to pre-trace at startup — and the
    engine's per-call cost is sublinear in batch size, so the ≤2× lane
    padding costs far less than it looks (and nothing at all in results:
    pad lanes cycle real queries and are sliced off)."""
    if m <= 1:
        return 1
    return min(1 << (m - 1).bit_length(), max_batch)


@dataclasses.dataclass
class ServingConfig:
    """Knobs for :class:`AnnServer`.

    Engine side (passed straight to :func:`repro.search.search`):
    ``k``, ``width``, ``n_entries``, ``backend``, ``nprobe``, ``metric``,
    ``dtype`` (distance stage: ``"f32"``/``"bf16"``/``"uint8"``) and
    ``rerank`` (staged dtypes re-rank the top ``rerank·k`` candidates
    exactly in f32).

    Batching side: a batch flushes at ``max_batch`` requests or when its
    oldest request has waited ``max_wait_ms`` — whichever trips first
    (``adaptive_window=True`` swaps the fixed window for
    :class:`~repro.serving.policy.AdaptiveWindow`).  ``max_pending`` bounds
    admitted-but-unserved requests; past it, ``admission="reject"`` errors
    the submitter and ``"shed"`` errors the oldest queued request instead.
    ``bucket_batches`` pads engine calls to power-of-two sizes (cycling
    real queries) so jitted backends see at most ``log2(max_batch)+1``
    shapes, and ``pretrace`` traces all of them before the first real
    batch (the worker does it off-loop at startup; requests submitted
    meanwhile just queue) — otherwise the first occurrence of each shape
    pays a multi-second jit trace inside some request's latency.
    ``pretrace_dtypes`` extends the warm-up to the full cross-product of
    the listed distance stages × bucket sizes: a deployment that accepts
    per-request ``dtype`` overrides (the worker groups batches by
    ``(nprobe, dtype)``) should list every stage it serves, or the first
    mixed-dtype batch hits a mid-traffic retrace.  Empty (the default)
    warms only the config-default ``dtype`` — the historical behavior,
    and the right one when traffic is single-stage.
    """

    k: int = 10
    width: int = 64
    n_entries: int = 16
    backend: str = "jax"
    nprobe: Any = None  # NprobeSpec: int, "auto", ("auto", margin), None
    dtype: str = "f32"  # distance stage; per-request overridable
    rerank: int = DEFAULT_RERANK  # staged dtypes re-rank rerank·k exactly
    metric: str | None = None
    max_batch: int = 64
    max_wait_ms: float = 2.0
    max_pending: int = 4096
    admission: str = "reject"
    adaptive_window: bool = False
    bucket_batches: bool = True
    pretrace: bool = True  # warm every bucketed shape before serving
    pretrace_dtypes: tuple = ()  # extra distance stages to warm (×buckets)
    run_in_executor: bool = True  # False: call the engine on the loop


@dataclasses.dataclass
class QueryResult:
    """What a ``submit()`` future resolves to."""

    ids: np.ndarray  # [k] int64, -1 padded
    latency_s: float  # end-to-end: submit → future resolution
    batch_size: int  # real occupancy of the engine call that served it
    # (a flush splits into one engine call per distinct nprobe override,
    # so this can be smaller than the flush size)


class AnnServer:
    """Async micro-batching front-end over :func:`repro.search.search`.

    Usage::

        async with AnnServer(index, data=data,
                             config=ServingConfig(backend="jax")) as srv:
            res = await srv.submit(query_vector)
            # res.ids, res.latency_s

    Accepts everything ``repro.search.search`` accepts as a target — a
    topology, a bare ``GlobalIndex`` + ``data``, or ``(ids, graphs)`` +
    ``data`` — so routed split serving and all registered backends work
    unchanged.  ``submit`` may carry per-request ``nprobe`` (e.g.
    ``"auto"``) and ``dtype`` (e.g. ``"uint8"``) overrides; the worker
    groups a flushed batch by the ``(nprobe, dtype)`` pair so mixed
    batches still make one engine call per distinct option set.

    ``tracer`` (default: the process-wide :func:`current_tracer`) draws
    every request as an async lane on the timeline — submit → queue wait
    → batch assembly → engine → rerank → future resolution, keyed by
    request id.  The tracer's clock **must share the server's** (pass
    ``Tracer(clock=time.monotonic)`` for the default server clock):
    request timestamps are taken with ``self.clock`` and emitted into the
    tracer's time base verbatim.  With the default no-op tracer the hot
    path pays a single ``enabled`` branch per request.
    """

    def __init__(self, index_or_shards, config: ServingConfig | None = None,
                 *, data: np.ndarray | None = None,
                 policy: SLOPolicy | None = None, clock=time.monotonic,
                 tracer=None):
        self.config = cfg = config or ServingConfig()
        self.topology = as_topology(index_or_shards, data,
                                    metric=cfg.metric or "l2")
        if cfg.metric is not None and self.topology.metric != cfg.metric:
            # caller passed a prebuilt topology with a different metric
            self.topology = dataclasses.replace(self.topology,
                                                metric=cfg.metric)
        parse_nprobe(cfg.nprobe)  # fail fast on a bad default spec
        parse_dtype(cfg.dtype)  # ...a bad distance stage
        for dt in cfg.pretrace_dtypes:  # ...a bad extra warm-up stage
            parse_dtype(dt)
        get_backend(cfg.backend)  # ...and on an unknown backend name
        if cfg.width < cfg.k:  # ...and before search() would refuse it
            raise ValueError(
                f"width ({cfg.width}) must be >= k ({cfg.k})"
            )
        self.stats = ServerStats()
        self.clock = clock
        self.tracer = current_tracer() if tracer is None else tracer
        self._scope = next(_SERVER_SEQ)
        self._rids = itertools.count()
        self._sig_guard = SignatureGuard()
        self.stats.registry.counter(*_POST_WARM_METRIC)  # expose at zero
        if self.tracer.enabled:
            # compile events land on the same timeline as the requests
            # they delay (idempotent)
            install_compile_listener()
        if policy is None:
            policy = (AdaptiveWindow(cfg.max_wait_ms, cfg.max_batch)
                      if cfg.adaptive_window else FixedWindow(cfg.max_wait_ms))
        self.policy = policy
        self._batcher = MicroBatcher(cfg.max_batch, cfg.max_wait_ms / 1e3)
        self._queue = RequestQueue(self._batcher, cfg.max_pending,
                                   admission=cfg.admission, clock=clock)
        self._worker_task: asyncio.Task | None = None
        self._inflight: list[PendingRequest] = []  # batch popped, unresolved
        self._dim = int(np.asarray(self.topology.data).shape[1])
        self.topology_generation = 0
        self.stats.registry.gauge(*_GENERATION_METRIC).set(0)

    # ---- live topology swap ---------------------------------------------

    def swap_topology(self, index_or_shards, *,
                      data: np.ndarray | None = None,
                      reason: str | None = None) -> int:
        """Atomically swap the served topology (epoch swap).

        The mutation layer (:class:`repro.live.LiveIndex`) builds the next
        generation copy-on-write while this server keeps answering on the
        current one; publishing is a single attribute store — atomic under
        the GIL — and the worker reads ``self.topology`` exactly once per
        engine batch (:meth:`_execute`), so every batch sees one
        consistent generation and in-flight futures resolve against the
        generation their batch started on.  No request is rejected or
        replayed across a swap.  Per-shard device caches carry over for
        every shard the new generation shares storage with (the live
        layer's snapshots are built for exactly that).

        ``reason`` labels the swap in metrics and the trace — e.g.
        ``"churn"`` for routine generation publishes vs ``"recovery"``
        when the generation came out of ``LiveIndex.load`` after a
        crash, so a dashboard can tell planned epochs from repaired
        ones.  Returns the new generation number.
        """
        topo = as_topology(index_or_shards, data,
                           metric=self.config.metric or "l2")
        dim = int(np.asarray(topo.data).shape[1])
        if dim != self._dim:
            raise ValueError(
                f"swapped topology dim {dim} != served dim {self._dim}"
            )
        if topo.metric != self.topology.metric:
            raise ValueError(
                f"swapped topology metric {topo.metric!r} != served "
                f"{self.topology.metric!r}"
            )
        self.topology = topo  # the swap: one atomic attribute store
        self.topology_generation += 1
        self.stats.registry.gauge(*_GENERATION_METRIC).set(
            self.topology_generation
        )
        self.stats.registry.counter(
            "serving_topology_swaps_total", "epoch swaps served",
            reason=reason or "unspecified",
        ).inc()
        if self.tracer.enabled:
            self.tracer.instant("serve.epoch_swap", track="serving",
                                generation=self.topology_generation,
                                reason=reason or "unspecified")
        return self.topology_generation

    # ---- lifecycle ------------------------------------------------------

    async def __aenter__(self) -> "AnnServer":
        self.start()
        return self

    async def __aexit__(self, exc_type, exc, tb) -> None:
        await self.stop()

    def start(self) -> None:
        if self._worker_task is not None:
            raise RuntimeError("server already started")
        self._worker_task = asyncio.get_running_loop().create_task(
            self._worker(), name="repro.serving.worker"
        )

    async def stop(self) -> None:
        """Drain: stop admitting, serve everything already queued, join."""
        if self._worker_task is None:
            return
        self._queue.close()
        task, self._worker_task = self._worker_task, None
        await task

    @property
    def depth(self) -> int:
        """Admitted-but-unserved requests (the SLO policy's input)."""
        return self._queue.depth()

    # ---- submission -----------------------------------------------------

    def submit_nowait(self, query: np.ndarray, *,
                      nprobe: Any = USE_DEFAULT,
                      dtype: Any = USE_DEFAULT,
                      t_submit: float | None = None) -> asyncio.Future:
        """Enqueue one query; returns the future (no await).

        ``nprobe`` / ``dtype`` override the server defaults per request
        (the worker groups a flushed batch by the pair, so mixed traffic
        still makes one engine call per distinct option set).
        ``t_submit`` backdates the request for open-loop measurement: a
        load generator that fell behind the arrival schedule can charge
        the scheduling slip to the request's latency, as a real network
        arrival would.  Raises :class:`ServerOverloadedError` when the
        bounded queue is full under the ``"reject"`` policy.
        """
        task = self._worker_task
        if task is None:
            raise RuntimeError(
                "server not started — use `async with AnnServer(...)` or "
                "call start() from a running event loop"
            )
        if task.done():  # crashed (a healthy worker runs until stop())
            exc = None if task.cancelled() else task.exception()
            raise RuntimeError("serving worker is no longer running") \
                from exc
        q = np.asarray(query, np.float32)
        if q.ndim != 1 or q.shape[0] != self._dim:
            raise ValueError(
                f"query must be a [{self._dim}] vector, got shape {q.shape}"
            )
        if nprobe is not USE_DEFAULT:
            parse_nprobe(nprobe)  # fail in the caller, not the worker
        if dtype is not USE_DEFAULT:
            parse_dtype(dtype)
        fut = asyncio.get_running_loop().create_future()
        req = PendingRequest(
            query=q, future=fut,
            t_submit=self.clock() if t_submit is None else t_submit,
            nprobe=self.config.nprobe if nprobe is USE_DEFAULT else nprobe,
            dtype=self.config.dtype if dtype is USE_DEFAULT else dtype,
            rid=next(self._rids),
        )
        try:
            shed = self._queue.submit(req)
        except ServerOverloadedError:
            self.stats.record_rejected()
            raise
        if shed is not None:
            self.stats.record_shed()
        # retune the open batch's window from the new depth
        self._batcher.max_wait_s = (
            self.policy.window_ms(self._queue.depth()) / 1e3
        )
        return fut

    async def submit(self, query: np.ndarray, *,
                     nprobe: Any = USE_DEFAULT,
                     dtype: Any = USE_DEFAULT,
                     t_submit: float | None = None) -> QueryResult:
        """Submit one query and await its :class:`QueryResult`."""
        return await self.submit_nowait(query, nprobe=nprobe, dtype=dtype,
                                        t_submit=t_submit)

    # ---- the worker -----------------------------------------------------

    async def _worker(self) -> None:
        try:
            await self._serve_loop()
        except BaseException as e:
            # a dead worker must not leave futures hanging: fail the
            # in-flight batch (already popped from the queue — e.g. a
            # cancellation landed mid-executor-call) plus everything still
            # admitted, and surface e via stop() / submit
            n = self._queue.fail_all(e)
            for req in self._inflight:
                if not req.future.done():
                    req.future.set_exception(e)
                    n += 1
            self._inflight = []
            self.stats.record_failed(n)
            raise

    async def _serve_loop(self) -> None:
        loop = asyncio.get_running_loop()
        if self.config.pretrace and self.config.bucket_batches:
            # without bucketing the engine sees one shape per occupancy —
            # pre-tracing the power-of-two set would warm the wrong shapes
            await loop.run_in_executor(None, self._pretrace)
        # from here on, a first-seen engine-call signature is a retrace
        # landing inside live traffic — exactly what the guard counts
        self._sig_guard.finish_warmup()
        while True:
            batch = await self._queue.next_batch()
            if batch is None:
                return
            t_flush = self.clock()
            for req in batch:
                req.t_flush = t_flush
            self._inflight = batch  # visible to the death handler
            try:
                if self.config.run_in_executor:
                    outs = await loop.run_in_executor(
                        None, self._execute, batch
                    )
                else:
                    outs = self._execute(batch)
            except Exception as e:  # engine failure: fail this batch only
                self.stats.record_failed(len(batch))
                for req in batch:
                    if not req.future.done():
                        req.future.set_exception(e)
                self._inflight = []
                continue
            now = self.clock()
            traced = self.tracer.enabled
            for req, (ids, group_size, t_eng0, t_eng1, rerank_s) in zip(
                    batch, outs):
                if req.future.done():  # submitter gave up (cancelled)
                    continue
                self.stats.record_completion(
                    req.t_submit, now,
                    queue_wait_s=req.t_flush - req.t_submit,
                    engine_s=t_eng1 - t_eng0,
                )
                req.future.set_result(QueryResult(
                    ids=ids, latency_s=max(now - req.t_submit, 0.0),
                    batch_size=group_size,
                ))
                if traced:
                    self._emit_request_trace(req, now, t_eng0, t_eng1,
                                             rerank_s)
            self._inflight = []

    def _emit_request_trace(self, req: PendingRequest, t_done: float,
                            t_eng0: float, t_eng1: float,
                            rerank_s: float) -> None:
        """One request's life as an async lane: the ``serve.request``
        parent plus contiguous child phases that tile it end to end
        (queue wait → batch assembly → engine → rerank → resolution), all
        keyed by the request id so overlapping requests render as
        separate lanes.  Emitted after resolution; timestamps are the
        server-clock readings the worker already took, so tracing adds no
        clock reads to the hot path."""
        tr = self.tracer
        aid = f"srv{self._scope}:req{req.rid}"
        t_rr0 = max(t_eng1 - rerank_s, t_eng0)
        tr.async_complete("serve.request", aid, req.t_submit, t_done,
                          cat="serving", track="requests", rid=req.rid)
        tr.async_complete("serve.queue_wait", aid, req.t_submit,
                          req.t_flush, cat="serving", track="requests")
        tr.async_complete("serve.batch", aid, req.t_flush, t_eng0,
                          cat="serving", track="requests")
        tr.async_complete("serve.engine", aid, t_eng0, t_rr0,
                          cat="serving", track="requests")
        tr.async_complete("serve.rerank", aid, t_rr0, t_eng1,
                          cat="serving", track="requests")
        tr.async_complete("serve.resolve", aid, t_eng1, t_done,
                          cat="serving", track="requests")

    def _pretrace(self) -> None:
        """Warm every batch shape the worker can produce (index vectors
        stand in for queries), so jit tracing is a startup cost instead of
        a latency spike on the first unlucky request of each occupancy.

        By default only the *config-default* ``(nprobe, dtype)`` path is
        warmed — warming every dtype would triple the startup cost for
        buckets single-stage traffic never hits.  A deployment that
        serves per-request ``dtype`` overrides lists its stages in
        ``pretrace_dtypes`` and gets the full dtypes × bucket-sizes
        cross-product warmed instead, so the first mixed-dtype flush
        doesn't pay a mid-traffic retrace (the worker groups batches by
        ``(nprobe, dtype)``, so each listed stage really is a distinct
        engine-call shape).  ``nprobe`` overrides (and the routed split
        driver's data-dependent per-shard group shapes) can still trace
        on first use.  With ``bucket_batches=False`` occupancies are
        unbounded-shape anyway, so there is nothing useful to warm (see
        ``_serve_loop``)."""
        cfg = self.config
        sizes = {bucket_batch_size(cfg.max_batch, cfg.max_batch)}
        b = 1
        while b < cfg.max_batch:
            sizes.add(b)
            b <<= 1
        dtypes = dict.fromkeys((cfg.dtype, *cfg.pretrace_dtypes))
        data = np.asarray(self.topology.data, np.float32)
        nprobe_key = parse_nprobe(cfg.nprobe)
        for size in sorted(sizes):
            qs = np.resize(data[: min(len(data), size)], (size, self._dim))
            for dtype in dtypes:
                self._sig_guard.warm(
                    (cfg.backend, size, nprobe_key, dtype)
                )
                search(self.topology, qs, cfg.k, backend=cfg.backend,
                       width=cfg.width, n_entries=cfg.n_entries,
                       nprobe=cfg.nprobe, dtype=dtype, rerank=cfg.rerank)

    def _execute(self, batch: list[PendingRequest]) -> list[tuple]:
        """One flushed batch → engine calls, grouped by the per-request
        ``(nprobe, dtype)`` option pair.

        Runs in an executor thread; touches no asyncio state.  Batches are
        bucket-padded by cycling real queries (the padded lanes recompute
        real work, so results are unaffected and stats can be rescaled).
        Each request's slot carries its engine call's ``(t0, t1)`` window
        (server-clock readings — cross-thread safe with the monotonic
        default) and the exact-rerank share of it, for the latency
        decomposition and the per-request trace lanes.
        """
        cfg = self.config
        clk = self.clock
        # read the served topology ONCE per batch: swap_topology() may
        # replace the attribute concurrently (atomic store from the loop
        # thread), and every engine call in this flush must answer against
        # one consistent generation
        topo = self.topology
        # key on the *parsed* nprobe spec so equivalent forms ("auto" vs
        # ("auto", DEFAULT_AUTO_MARGIN), 2 vs np.int64(2)) share one
        # engine call instead of splitting the batch; dtype is already
        # canonical after parse_dtype at submit time
        groups: dict[tuple, tuple[Any, str, list[int]]] = {}
        for i, req in enumerate(batch):
            key = (parse_nprobe(req.nprobe), req.dtype)
            groups.setdefault(key, (req.nprobe, req.dtype, []))[2].append(i)
        out: list[tuple | None] = [None] * len(batch)
        for key, (nprobe, dtype, idxs) in groups.items():
            queries = np.stack([batch[i].query for i in idxs])
            m = len(idxs)
            b = bucket_batch_size(m, cfg.max_batch) if cfg.bucket_batches \
                else m
            if b > m:
                queries = np.resize(queries, (b, queries.shape[1]))
            _, post_warm = self._sig_guard.observe((cfg.backend, b) + key)
            if post_warm:  # mid-traffic retrace risk: shape never warmed
                # resolved through the live stats object: benches swap
                # self.stats for a fresh window and must keep the count
                self.stats.registry.counter(*_POST_WARM_METRIC).inc()
                if self.tracer.enabled:
                    self.tracer.instant("serve.retrace_risk", track="jit",
                                        backend=cfg.backend, batch=b,
                                        dtype=dtype)
            t0 = clk()
            with collect_stages() as stages:
                ids, st = search(
                    topo, queries, cfg.k, backend=cfg.backend,
                    width=cfg.width, n_entries=cfg.n_entries, nprobe=nprobe,
                    dtype=dtype, rerank=cfg.rerank,
                )
            t1 = clk()
            self.stats.observe_batch(m, b, st, t1 - t0)
            rerank_s = stages.get("search.rerank", 0.0)
            for j, i in enumerate(idxs):
                out[i] = (ids[j], m, t0, t1, rerank_s)
        return out  # type: ignore[return-value]
