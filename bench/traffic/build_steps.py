"""Whole index builds, back to back.

Set-up runs one build of the same data, which compiles every shard shape.
The window then starts ``build_scalegann`` while time is left and lets the
build in progress finish; ``build_vec_per_s`` is the vectors indexed over
the wall seconds of all those builds.  After the window the last merged
graph is searched with the run's queries, and those answers are judged;
so is the first neighbour of every row in the last build's shard graphs
(:func:`bench.check.nn_gap`), which is the exact kNN's nearest.
"""

from __future__ import annotations

import time

import numpy as np


def _summary(res) -> dict:
    return {
        "partition_s": res.partition_s,
        "shards_s": res.wall_build_s,
        "merge_s": res.merge_s,
        "shard_sizes": [len(s.ids) for s in res.shards],
    }


class Driver:
    def __init__(self, ctx):
        from repro.configs.base import IndexConfig

        self.ctx = ctx
        self.icfg = IndexConfig(**ctx.config["index"])
        self.n_queries = ctx.params["queries"]
        self.last = None

    def _build(self):
        from repro.core.builder import build_scalegann

        return build_scalegann(self.ctx.dep.data, self.icfg, algo="cagra",
                               max_retries=0)

    def setup(self) -> None:
        self.last = self._build()

    def window(self, seconds: float, prof) -> dict:
        builds = []
        prof.start()
        t0 = time.monotonic()
        while time.monotonic() - t0 < seconds:
            self.last = self._build()
            builds.append(_summary(self.last))
        t1 = time.monotonic()
        prof.stop()
        n = len(self.ctx.dep.data)
        return {
            "window_s": t1 - t0,
            "attempted": len(builds),
            "failed": 0,
            "metrics": {"build_vec_per_s": len(builds) * n / (t1 - t0)},
            "obs": {"builds": builds, "dim": self.ctx.dep.data.shape[1],
                    "knn_k": self.icfg.build_degree + 1},
        }

    def answers(self):
        from repro.search import search

        sp = self.ctx.params["search"]
        q = self.ctx.pool_f32[: self.n_queries]
        ids = np.concatenate([
            search(self.last.index, q[s:s + sp["chunk"]], self.ctx.k,
                   backend=sp["backend"], data=self.ctx.dep.data,
                   width=sp["width"], n_entries=sp["n_entries"],
                   metric=self.ctx.config["metric"])[0]
            for s in range(0, len(q), sp["chunk"])
        ])
        return np.arange(len(q)), ids, 0

    def first_neighbours(self) -> list:
        """``[(ids, first)]`` per shard of the last build: the shard's rows
        as base ids and the first entry of each row's graph list as a base
        id (-1 where the list is empty)."""
        out = []
        for shard, graph in zip(self.last.shards, self.last.shard_graphs):
            ids = np.asarray(shard.ids, np.int64)
            first = np.asarray(graph, np.int64)[:, 0]
            out.append((ids, np.where(first >= 0, ids[np.maximum(first, 0)],
                                      -1)))
        return out

    def close(self) -> None:
        self.last = None
