"""Batching semantics + end-to-end behavior of ``repro.serving``.

The :class:`MicroBatcher` tests run on a fake clock — requests carry
explicit ``t_submit`` stamps and ``poll(now)`` takes explicit time — so the
flush rules (size flush at ``max_batch``, deadline flush at ``max_wait_ms``,
whichever first) are proven deterministically, with no sleeps and no timing
slack.  The server integration tests use a ring-graph shard small enough
that beam search visits every vector, making per-future result routing
checkable against exact brute force.
"""

import asyncio

import numpy as np
import pytest

from repro.search import ShardTopology
from repro.search.types import SearchStats
from repro.serving import (AdaptiveWindow, AnnServer, FixedWindow,
                           MicroBatcher, PendingRequest, RequestQueue,
                           ServerOverloadedError, ServerStats, ServingConfig)


def _req(t_submit: float, future=None) -> PendingRequest:
    return PendingRequest(query=None, future=future, t_submit=t_submit)


@pytest.fixture(scope="module")
def ring():
    """One 40-vector shard with a ring graph: width 64 > n, so beam search
    visits everything and results are exactly brute force."""
    rng = np.random.default_rng(0)
    data = rng.normal(size=(40, 8)).astype(np.float32)
    g = np.stack([(np.arange(40) + s) % 40 for s in range(1, 6)],
                 axis=1).astype(np.int32)
    topo = ShardTopology(data=data,
                         shard_ids=[np.arange(40, dtype=np.int64)],
                         shard_graphs=[g])
    return data, topo


# ---- (a) size flush ------------------------------------------------------

def test_flush_at_max_batch():
    mb = MicroBatcher(max_batch=4, max_wait_s=1e9)  # deadline never trips
    reqs = [_req(float(i)) for i in range(9)]
    for r in reqs[:3]:
        assert mb.add(r) is None
    batch = mb.add(reqs[3])
    assert batch == reqs[:4]  # oldest first, exactly max_batch
    assert len(mb) == 0
    # the next four fill a fresh batch
    for r in reqs[4:7]:
        assert mb.add(r) is None
    assert mb.add(reqs[7]) == reqs[4:8]
    assert mb.add(reqs[8]) is None  # a 9th starts batch three


# ---- (b) deadline flush --------------------------------------------------

def test_flush_at_max_wait():
    mb = MicroBatcher(max_batch=100, max_wait_s=0.005)
    a, b = _req(0.0), _req(0.003)
    assert mb.add(a) is None and mb.add(b) is None
    # window counts from the *oldest* pending request
    assert mb.deadline() == pytest.approx(0.005)
    assert mb.poll(0.00499) is None
    assert mb.poll(0.005) == [a, b]
    assert len(mb) == 0 and mb.poll(1.0) is None  # empty: nothing to flush
    # the next request opens a new window from its own submit time
    c = _req(0.010)
    mb.add(c)
    assert mb.deadline() == pytest.approx(0.015)
    assert mb.poll(0.014) is None
    assert mb.poll(0.015) == [c]


def test_window_retune_moves_open_deadline():
    """An SLOPolicy retunes ``max_wait_s`` mid-batch; the derived deadline
    must follow (depth spikes should flush an already-open batch early)."""
    mb = MicroBatcher(max_batch=100, max_wait_s=0.050)
    mb.add(_req(0.0))
    assert mb.poll(0.010) is None  # 50 ms window still open
    mb.max_wait_s = 0.002  # policy collapsed the window
    assert mb.poll(0.010) is not None  # 10 ms > 2 ms → flush now


def test_adaptive_window_policy():
    p = AdaptiveWindow(max_wait_ms=10.0, max_batch=10, min_wait_ms=0.5)
    assert p.window_ms(0) == pytest.approx(10.0)
    assert p.window_ms(5) == pytest.approx(5.0)
    assert p.window_ms(10) == pytest.approx(0.5)  # floor, not 0
    assert p.window_ms(1000) == pytest.approx(0.5)
    assert FixedWindow(3.0).window_ms(1000) == pytest.approx(3.0)


# ---- (c) results route to the right futures ------------------------------

def test_results_route_to_correct_future(ring):
    """Interleaved submit order; every future must resolve to *its own*
    query's exact top-k, not its batch-neighbor's."""
    data, topo = ring
    d2 = ((data[:, None, :] - data[None, :, :]) ** 2).sum(-1)

    async def main():
        sc = ServingConfig(backend="numpy", k=5, width=64, max_batch=4,
                           max_wait_ms=50.0)
        async with AnnServer(topo, config=sc) as srv:
            order = np.random.default_rng(3).permutation(len(data))
            futs = {int(i): srv.submit_nowait(data[i]) for i in order}
            for i, f in futs.items():
                res = await f
                expect = np.argsort(d2[i], kind="stable")[:5]
                assert res.ids[0] == i  # own vector is the 1-NN
                assert set(res.ids.tolist()) == set(expect.tolist()), i
                assert res.latency_s >= 0.0
                assert 1 <= res.batch_size <= 4
        assert srv.stats.n_completed == len(data)
        occ = srv.stats.occupancy()
        assert occ["max"] <= 4

    asyncio.run(main())


# ---- (d) bounded-queue admission -----------------------------------------

def test_bounded_queue_rejection():
    async def main():
        loop = asyncio.get_running_loop()
        q = RequestQueue(MicroBatcher(100, 1e9), max_pending=3,
                         admission="reject")
        reqs = [_req(0.0, loop.create_future()) for _ in range(4)]
        for r in reqs[:3]:
            assert q.submit(r) is None
        with pytest.raises(ServerOverloadedError, match="full"):
            q.submit(reqs[3])
        assert q.depth() == 3  # the rejected request was never admitted

    asyncio.run(main())


def test_bounded_queue_shed_oldest():
    async def main():
        loop = asyncio.get_running_loop()
        q = RequestQueue(MicroBatcher(100, 1e9), max_pending=3,
                         admission="shed")
        reqs = [_req(float(i), loop.create_future()) for i in range(5)]
        for r in reqs[:3]:
            q.submit(r)
        assert q.submit(reqs[3]) is reqs[0]  # oldest made room
        assert q.submit(reqs[4]) is reqs[1]
        for old in reqs[:2]:
            with pytest.raises(ServerOverloadedError, match="shed"):
                old.future.result()
        assert q.depth() == 3
        # the survivors drain in order on close
        q.close()
        assert await q.next_batch() == reqs[2:5]
        assert await q.next_batch() is None

    asyncio.run(main())


def test_server_reject_surfaces_to_submitter(ring):
    data, topo = ring

    async def main():
        sc = ServingConfig(backend="numpy", k=3, width=16, max_batch=4,
                           max_wait_ms=200.0, max_pending=4,
                           admission="reject")
        async with AnnServer(topo, config=sc) as srv:
            futs = []
            rejected = 0
            for i in range(12):
                try:
                    futs.append(srv.submit_nowait(data[i]))
                except ServerOverloadedError:
                    rejected += 1
            assert rejected > 0
            outs = await asyncio.gather(*futs)
            assert len(outs) + rejected == 12
        assert srv.stats.n_rejected == rejected
        assert srv.stats.n_completed == len(futs)

    asyncio.run(main())


# ---- queue drain / shutdown ----------------------------------------------

def test_close_drains_pending():
    async def main():
        loop = asyncio.get_running_loop()
        q = RequestQueue(MicroBatcher(3, 1e9), max_pending=100)
        reqs = [_req(float(i), loop.create_future()) for i in range(5)]
        for r in reqs:
            q.submit(r)
        q.close()
        with pytest.raises(RuntimeError, match="clos"):
            q.submit(_req(9.0, loop.create_future()))
        # one size-flushed batch already waiting, then the remainder
        assert await q.next_batch() == reqs[:3]
        assert await q.next_batch() == reqs[3:]
        assert await q.next_batch() is None

    asyncio.run(main())


def test_server_stop_answers_everything(ring):
    """`async with` exit must serve every admitted request, not drop them."""
    data, topo = ring

    async def main():
        sc = ServingConfig(backend="numpy", k=3, width=16, max_batch=64,
                           max_wait_ms=10_000.0)  # would wait 10 s...
        async with AnnServer(topo, config=sc) as srv:
            futs = [srv.submit_nowait(data[i]) for i in range(6)]
        # ...but exiting the context drained immediately
        outs = [f.result() for f in futs]
        assert all(o.ids[0] == i for i, o in enumerate(outs))

    asyncio.run(main())


# ---- validation + telemetry ----------------------------------------------

def test_submit_validation(ring):
    data, topo = ring

    async def main():
        async with AnnServer(topo, config=ServingConfig(
                backend="numpy", k=3, width=16)) as srv:
            with pytest.raises(ValueError, match="vector"):
                srv.submit_nowait(np.zeros((3, 8), np.float32))
            with pytest.raises(ValueError, match="vector"):
                srv.submit_nowait(np.zeros(7, np.float32))
            with pytest.raises(ValueError, match="nprobe"):
                srv.submit_nowait(data[0], nprobe="always")

    asyncio.run(main())


def test_submit_before_start_raises(ring):
    _, topo = ring
    srv = AnnServer(topo, config=ServingConfig(backend="numpy"))
    with pytest.raises(RuntimeError, match="not started"):
        srv.submit_nowait(np.zeros(8, np.float32))


def test_bad_config_fails_at_construction(ring):
    _, topo = ring
    with pytest.raises(ValueError, match="backend"):
        AnnServer(topo, config=ServingConfig(backend="cuda"))
    with pytest.raises(ValueError, match="nprobe"):
        AnnServer(topo, config=ServingConfig(backend="numpy", nprobe=0))
    with pytest.raises(ValueError, match="width"):
        AnnServer(topo, config=ServingConfig(backend="numpy", k=10,
                                             width=4))


def test_worker_death_fails_futures_not_hangs(ring):
    """If the worker dies outside the per-batch guard (here: pretrace
    explodes at startup), every admitted future must fail promptly — a
    hung await would be strictly worse — and later submits must say the
    worker is gone."""
    data, topo = ring

    async def main():
        sc = ServingConfig(backend="numpy", k=3, width=16, max_batch=64,
                           max_wait_ms=5.0, pretrace=True)
        srv = AnnServer(topo, config=sc)

        def boom():
            raise RuntimeError("pretrace exploded")

        srv._pretrace = boom
        srv.start()
        task = srv._worker_task
        fut = srv.submit_nowait(data[0])
        with pytest.raises(RuntimeError, match="exploded"):
            await fut
        await asyncio.wait({task})  # let the task finish unwinding
        with pytest.raises(RuntimeError, match="no longer running"):
            srv.submit_nowait(data[1])
        with pytest.raises(RuntimeError, match="exploded"):
            await srv.stop()
        assert srv.stats.n_failed >= 1

    asyncio.run(main())


def test_engine_error_fails_batch_but_server_survives(ring):
    """An engine failure is scoped to its batch: those futures get the
    exception, and the server keeps serving later requests."""
    data, topo = ring

    async def main():
        sc = ServingConfig(backend="numpy", k=3, width=16, max_batch=64,
                           max_wait_ms=5.0, pretrace=False)
        async with AnnServer(topo, config=sc) as srv:
            real = srv._execute
            srv._execute = None  # the next batch blows up in the worker
            fut = srv.submit_nowait(data[0])
            with pytest.raises(TypeError):
                await fut
            srv._execute = real  # engine recovers
            res = await srv.submit(data[1])
            assert res.ids[0] == 1
        assert srv.stats.n_failed == 1
        assert srv.stats.n_completed == 1

    asyncio.run(main())


def test_shed_victim_is_globally_oldest():
    """With size-flushed batches waiting in _ready, shedding must evict
    the globally oldest request (in _ready), not the open batch's."""
    async def main():
        loop = asyncio.get_running_loop()
        q = RequestQueue(MicroBatcher(2, 1e9), max_pending=3,
                         admission="shed")
        reqs = [_req(float(i), loop.create_future()) for i in range(4)]
        for r in reqs[:3]:  # 0,1 size-flush into _ready; 2 stays open
            q.submit(r)
        assert q.submit(reqs[3]) is reqs[0]
        with pytest.raises(ServerOverloadedError):
            reqs[0].future.result()
        assert not reqs[2].future.done()  # the open batch was untouched

    asyncio.run(main())


def test_equivalent_nprobe_specs_share_one_engine_call(ring):
    """Spec forms that parse identically ("auto" vs the explicit default
    tuple, int vs np.int64) must not split a flushed batch."""
    data, topo = ring
    from repro.search import DEFAULT_AUTO_MARGIN

    async def main():
        sc = ServingConfig(backend="numpy", k=3, width=16, max_batch=4,
                           max_wait_ms=50.0)
        async with AnnServer(topo, config=sc) as srv:
            outs = await asyncio.gather(
                srv.submit(data[0], nprobe="auto"),
                srv.submit(data[1], nprobe=("auto", DEFAULT_AUTO_MARGIN)),
                srv.submit(data[2], nprobe=2),
                srv.submit(data[3], nprobe=np.int64(2)),
            )
        assert [o.ids[0] for o in outs] == [0, 1, 2, 3]
        # one flush, two parsed option groups, not four
        assert srv.stats.n_batches == 2
        # batch_size reports the engine call's occupancy, not the flush's
        assert [o.batch_size for o in outs] == [2, 2, 2, 2]

    asyncio.run(main())


def test_mixed_dtype_batches_group_without_cross_contamination(
        ring, monkeypatch):
    """One flush of mixed per-request ``(nprobe, dtype)`` traffic must make
    exactly one engine call per distinct option pair (no splitting of
    equivalent specs, no merging of different ones), route every result to
    its *own* future, and keep jit pre-tracing to the config-default path
    — extra dtypes must not add startup trace buckets.

    Runs on a fake clock: submit stamps are explicit and the server's
    clock is advanced by hand, so the recorded latencies are exact
    arithmetic, not wall-time."""
    data, topo = ring
    calls = []
    import repro.serving.server as srv_mod

    real_search = srv_mod.search

    def recording_search(t, queries, k, **kw):
        calls.append((len(queries), kw.get("nprobe"), kw.get("dtype")))
        return real_search(t, queries, k, **kw)

    monkeypatch.setattr(srv_mod, "search", recording_search)
    now = {"t": 0.0}

    async def main():
        sc = ServingConfig(backend="numpy", k=3, width=16, max_batch=8,
                           max_wait_ms=50.0, pretrace=True)
        async with AnnServer(topo, config=sc,
                             clock=lambda: now["t"]) as srv:
            combos = [(None, "f32"), (None, "uint8"), (None, "bf16"),
                      (1, "uint8")]
            futs = []
            for i in range(8):  # fills max_batch: one size-flush
                nprobe, dtype = combos[i % 4]
                futs.append(srv.submit_nowait(
                    data[i], nprobe=nprobe, dtype=dtype,
                    t_submit=i * 0.001))
            now["t"] = 1.0  # completions are stamped by the fake clock
            outs = await asyncio.gather(*futs)
        # --- no cross-contamination: each future got its own query's NN
        assert [int(o.ids[0]) for o in outs] == list(range(8))
        # each engine call saw only its group's 2 requests
        assert [o.batch_size for o in outs] == [2] * 8
        # fake-clock latency: exactly (1.0 - submit stamp)
        for i, o in enumerate(outs):
            assert o.latency_s == pytest.approx(1.0 - i * 0.001)
        # --- pre-trace warmed only the default (nprobe, dtype) path, one
        # call per power-of-two bucket (no extra buckets for overrides)
        pre = calls[: len(calls) - 4]
        assert sorted(size for size, _, _ in pre) == [1, 2, 4, 8]
        assert all(np is None and dt == "f32" for _, np, dt in pre)
        # --- the flush split into exactly one call per distinct pair
        flush = calls[-4:]
        assert sorted((str(np), dt) for _, np, dt in flush) == [
            ("1", "uint8"), ("None", "bf16"), ("None", "f32"),
            ("None", "uint8"),
        ]
        assert all(size == 2 for size, _, _ in flush)
        assert srv.stats.n_batches == 4
        snap = srv.stats.snapshot()
        # engine telemetry splits quantized vs re-rank work (f32-only
        # traffic would report 0 for both)
        assert snap["quantized_distance_computations_per_query"] > 0
        assert snap["rerank_distance_computations_per_query"] > 0

    asyncio.run(main())


def test_per_request_dtype_validation(ring):
    data, topo = ring

    async def main():
        async with AnnServer(topo, config=ServingConfig(
                backend="numpy", k=3, width=16)) as srv:
            with pytest.raises(ValueError, match="dtype"):
                srv.submit_nowait(data[0], dtype="fp4")

    asyncio.run(main())
    with pytest.raises(ValueError, match="dtype"):
        AnnServer(topo, config=ServingConfig(backend="numpy",
                                             dtype="int4"))


def test_cancellation_fails_inflight_batch(ring):
    """A worker cancelled mid-engine-call must fail the popped batch's
    futures (fail_all can't see them — they left the queue already)."""
    data, topo = ring

    async def main():
        sc = ServingConfig(backend="numpy", k=3, width=16, max_batch=2,
                           max_wait_ms=1.0, pretrace=False)
        srv = AnnServer(topo, config=sc)
        srv.start()
        import time as _time
        real = srv._execute
        srv._execute = lambda batch: (_time.sleep(0.2), real(batch))[1]
        f1 = srv.submit_nowait(data[0])
        f2 = srv.submit_nowait(data[1])  # size-flush: batch goes in-flight
        await asyncio.sleep(0.05)  # worker is now inside the executor call
        task = srv._worker_task
        task.cancel()
        await asyncio.wait({task})
        for f in (f1, f2):
            assert f.done()
            with pytest.raises(asyncio.CancelledError):
                f.result()

    asyncio.run(main())


def test_bucket_batch_size_is_pow2_capped():
    """The worker's engine-call shapes: powers of two, capped at
    max_batch, so a server traces at most log2(max_batch)+1 jit shapes."""
    from repro.serving.server import bucket_batch_size

    got = [bucket_batch_size(m, 64) for m in (1, 2, 3, 4, 5, 8, 9, 33, 64)]
    assert got == [1, 2, 4, 4, 8, 8, 16, 64, 64]
    assert bucket_batch_size(40, 32) == 32  # never exceeds max_batch


def test_server_stats_accounting():
    st = ServerStats()
    for ms in (1.0, 2.0, 3.0, 4.0, 100.0):
        st.record_completion(0.0, ms / 1e3)
    lat = st.latency_ms()
    assert lat["p50"] == pytest.approx(3.0)
    assert lat["max"] == pytest.approx(100.0)
    assert st.qps() == pytest.approx(5 / 0.1)
    # padding-scaled engine accounting: 8 lanes served 3 real requests
    st.observe_batch(3, 8, SearchStats(n_distance_computations=800,
                                       n_hops=80, n_queries=8), 0.01)
    assert st.dist_comps == pytest.approx(300.0)
    assert st.hops == pytest.approx(30.0)
    snap = st.snapshot()
    assert snap["padding_fraction"] == pytest.approx(5 / 8)
    assert snap["batch_occupancy"]["histogram"] == {"3": 1}


def test_serve_vs_serving_namespaces():
    """`repro.serving` is the one serving package, the ANN query server:
    the package holds no LM decode engine beside it to confuse it with."""
    import pkgutil

    import repro
    import repro.serving as ann

    packages = {m.name for m in pkgutil.iter_modules(repro.__path__)}
    assert "serving" in packages and "serve" not in packages
    assert "ANN" in ann.__doc__
    assert "AnnServer" in ann.__all__
