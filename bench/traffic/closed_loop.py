"""A closed loop of a fixed number of clients, each sending its next query
when the previous one is answered.

Batch jobs that keep a fixed number of requests in flight: the server never
waits for arrivals, so the engine sets the rate.  A request counts in the
window when it was sent before the window closed; ``serve_qps`` counts those
answered before it closed.  Queries follow one order drawn from the seed,
taken by whichever client sends next.
"""

from __future__ import annotations

import asyncio
import itertools

import numpy as np

from bench.serving import (DRAIN_S, TRACE_STREAM, WARM_STREAM,
                           WINDOW_STREAM, Record, Serving, answers, collect,
                           stream)

ORDER_LEN = 1 << 16


class Driver:
    def __init__(self, ctx):
        self.ctx = ctx
        self.p = ctx.params
        self.s = Serving(ctx)
        self.rec: Record | None = None

    def setup(self) -> None:
        self.s.start()
        self.s.run(self._phase(self.p["warm_seconds"], WARM_STREAM))

    async def _phase(self, seconds: float, which: int):
        srv = self.s.srv
        order = itertools.cycle(stream(self.ctx.seed, which).integers(
            0, len(self.ctx.pool_f32), ORDER_LEN).tolist())
        rows: list = []
        t_due: list = []
        futs: list = []
        t0 = srv.clock()
        t_close = t0 + seconds

        async def client():
            while srv.clock() < t_close:
                row = next(order)
                rows.append(row)
                t_due.append(srv.clock())
                fut = srv.submit_nowait(self.ctx.pool_f32[row])
                futs.append(fut)
                try:
                    await fut
                except Exception:  # noqa: BLE001 - counted as unanswered
                    pass

        try:
            await asyncio.wait_for(
                asyncio.gather(*(client() for _ in range(self.p["clients"]))),
                timeout=seconds + DRAIN_S)
        except TimeoutError:  # what is still waiting counts as unanswered
            pass
        due = np.asarray(t_due)
        t_done, ids, _ = collect(futs, due)
        rec = Record(rows=np.asarray(rows, np.int64), t_due=due,
                     t_done=t_done, ids=ids)
        return rec, t0, t_close

    def window(self, seconds: float, prof) -> dict:
        self.s.reset_stats()
        c0 = self.ctx.compiles()
        rec, t0, t_close = self.s.run(self._phase(seconds, WINDOW_STREAM))
        compiled = self.ctx.compiled_since(c0)
        self.rec = rec
        stats = self.s.srv.stats.snapshot()
        traced = self.s.traced(prof, lambda: self._phase(
            self.p["trace_seconds"], TRACE_STREAM))
        return {
            "window_s": seconds,
            "attempted": len(rec.rows),
            "failed": int((~np.isfinite(rec.t_done)).sum()),
            "metrics": {
                "serve_qps": float((rec.t_done <= t_close).sum() / seconds),
            },
            "obs": {
                "server": stats,
                "compiles_in_window": len(compiled),
                "compiled_in_window": compiled,
                "engine_calls_traced": traced,
                "longest_pause_ms": _longest_pause(rec.t_done, t0,
                                                   t_close) * 1e3,
            },
        }

    def answers(self):
        return answers(self.rec, self.ctx.k)

    def close(self) -> None:
        self.s.close()


def _longest_pause(t_done: np.ndarray, t0: float, t_close: float) -> float:
    """The longest stretch of the window in which no request was answered,
    in seconds: a stall of the engine or the host shows here."""
    t = np.sort(t_done[t_done <= t_close])
    return float(np.diff(np.concatenate([[t0], t, [t_close]])).max())
