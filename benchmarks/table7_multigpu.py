"""Paper Table VII: multi-accelerator shard-build parallelism.

Two measurements:
  1. real thread-pool workers (1/2/4) over the actual shard builds —
     wall-clock speedup on this container (bounded by CPU cores);
  2. the scheduler simulator over the *measured* per-shard times for
     1/2/4/8 instances — the paper's near-linear scaling claim, free of
     host-core contention.
"""

from repro.common.compile_cache import enable_compile_cache
from repro.configs.base import IndexConfig
from repro.core.builder import build_scalegann
from repro.core.scheduler import (RuntimeModel, Scheduler,
                                  make_ondemand_pool, make_tasks)

from benchmarks.common import Rows, dataset


def main() -> Rows:
    rows = Rows("table7_multigpu")
    ds = dataset("deep_analog")
    cfg = IndexConfig(n_clusters=8, degree=16, build_degree=32,
                      block_size=768)
    res1 = build_scalegann(ds.data, cfg, n_workers=1)
    rows.add("workers1.wall_s", res1.wall_build_s)
    for n in (2, 4):
        res = build_scalegann(ds.data, cfg, n_workers=n)
        rows.add(f"workers{n}.wall_s", res.wall_build_s)
        rows.add(f"workers{n}.speedup", res1.wall_build_s / res.wall_build_s)

    # scheduler sim over measured shard times (ms granularity)
    sizes = [max(int(t * 1000), 1) for t in res1.per_shard_s]
    rm = RuntimeModel(seconds_per_vector=1e-3)
    m1 = Scheduler(make_tasks(sizes), make_ondemand_pool(1), rm).run()
    for n in (2, 4, 8):
        mk = Scheduler(make_tasks(sizes), make_ondemand_pool(n), rm).run()
        rows.add(f"sim{n}.speedup", m1.makespan_s / mk.makespan_s)
    rows.add("claim.near_linear_sim4",
             m1.makespan_s / Scheduler(
                 make_tasks(sizes), make_ondemand_pool(4), rm
             ).run().makespan_s > 2.5)
    return rows


if __name__ == "__main__":
    enable_compile_cache()
    main()
