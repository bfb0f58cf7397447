"""Open-loop Poisson arrivals of single queries at the cell's fixed rate.

Independent users: each request is sent on its schedule whether or not
earlier ones were answered, stamped with its scheduled send time, and timed
from that time to the resolution of its future, so a stall is charged to
every request it delays.  Every seed gets the same count of arrivals
(``rate_qps × seconds``) and the same set of inter-arrival gaps (the
exponential distribution's quantiles), in an order drawn from the seed;
queries are drawn uniformly from the run's pool.
"""

from __future__ import annotations

import asyncio

import numpy as np

from bench.serving import (TRACE_STREAM, WARM_STREAM, WINDOW_STREAM, Record,
                           Serving, answers, collect, stream)


def schedule(rate: float, seconds: float, pool: int, rng):
    """``(offsets [n] s, pool rows [n])`` of one phase's arrivals."""
    n = max(1, round(rate * seconds))
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n) / rate
    gaps *= seconds / gaps.sum()
    rng.shuffle(gaps)
    rows = rng.integers(0, pool, n)
    return np.concatenate([[0.0], np.cumsum(gaps)[:-1]]), rows


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
        from repro.serving.queue import ServerOverloadedError

        srv = self.s.srv
        offs, rows = schedule(self.p["rate_qps"], seconds,
                              len(self.ctx.pool_f32),
                              stream(self.ctx.seed, which))
        t0 = srv.clock()
        t_due = t0 + offs
        t_close = t0 + seconds
        futs: list = [None] * len(rows)
        late = 0.0
        for j, row in enumerate(rows):
            delay = t_due[j] - srv.clock()
            if delay > 0:
                await asyncio.sleep(delay)
            late = max(late, srv.clock() - t_due[j])
            try:
                futs[j] = srv.submit_nowait(self.ctx.pool_f32[row],
                                            t_submit=t_due[j])
            except ServerOverloadedError:
                pass
        await self.s.drain(futs, t_close)
        t_done, ids, _ = collect(futs, t_due)
        rec = Record(rows=rows, t_due=t_due, t_done=t_done, ids=ids)
        return rec, t_close, late

    def window(self, seconds: float, prof) -> dict:
        self.s.reset_stats()
        c0 = self.ctx.compiles()
        rec, t_close, late = self.s.run(self._phase(seconds, WINDOW_STREAM))
        compiled = self.ctx.compiled_since(c0)
        self.rec = rec
        stats = self.s.srv.stats.snapshot()
        traced = self.s.traced(prof, lambda: self._phase(
            self.p["trace_seconds"], TRACE_STREAM))
        lat_ms = (rec.t_done - rec.t_due) * 1e3
        answered = np.isfinite(rec.t_done)
        return {
            "window_s": seconds,
            "attempted": len(rec.rows),
            "failed": int((~answered).sum()),
            "metrics": {
                "serve_qps": float((rec.t_done <= t_close).sum() / seconds),
            },
            "obs": {
                # every request due in the window, never-answered ones at inf
                "p95_ms": float(np.percentile(lat_ms, 95, method="higher")),
                "server": stats,
                "compiles_in_window": len(compiled),
                "compiled_in_window": compiled,
                "engine_calls_traced": traced,
                "generator_late_ms": late * 1e3,
            },
        }

    def answers(self):
        return answers(self.rec, self.ctx.k)

    def close(self) -> None:
        self.s.close()
