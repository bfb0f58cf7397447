"""Paper §VI-C: spot-instance cost analysis.

1. Reproduces the paper's worked example exactly (DiskANN ≥ $67.3 vs
   ScaleGANN ≤ $11.1 on Laion100M → ~6× cheaper).
2. Runs the same arithmetic over a *simulated* spot pool with preemptions,
   including the rescheduling overhead the paper's model omits (beyond-paper
   extension: the overhead is measured, not assumed zero).
"""

from repro.common.compile_cache import enable_compile_cache
from repro.core import cost_model
from repro.core.scheduler import (RuntimeModel, Scheduler, V100_SPOT,
                                  Instance, InstanceType, make_spot_pool,
                                  make_tasks)

from benchmarks.common import Rows


def main() -> Rows:
    rows = Rows("cost_analysis")
    ex = cost_model.paper_example()
    rows.add("paper.diskann_usd", ex["diskann_cost"])
    rows.add("paper.scalegann_usd", ex["scalegann_cost"])
    rows.add("paper.cost_ratio", ex["speedup_cost"])
    rows.add("claim.matches_paper_67_vs_11",
             abs(ex["diskann_cost"] - 67.3) < 1.0
             and abs(ex["scalegann_cost"] - 11.1) < 1.0)

    # simulated flaky pool: 16 shards ≈ Sift100M geometry, exp lifetimes
    rm = RuntimeModel(seconds_per_vector=1e-3)
    sizes = [160_000] * 16  # ≈160 s/shard (paper: "each ~160 seconds")
    pool = make_spot_pool(4, mean_lifetime_s=900.0, seed=5)
    for i in pool:
        i.lifetime_s = min(i.lifetime_s, 3600.0 + 300 * i.iid)
    sim = Scheduler(make_tasks(sizes), pool, rm, checkpoint_resume=True,
                    checkpoint_interval_s=30.0).run()
    xfer = cost_model.transfer_time_s(16, 16e9)
    cost = cost_model.scalegann_cost(sim.makespan_s + 1800.0,
                                     sim.gpu_active_s, xfer)
    rows.add("sim.makespan_s", sim.makespan_s)
    rows.add("sim.gpu_active_s", sim.gpu_active_s)
    rows.add("sim.preemptions", sim.n_preemptions)
    rows.add("sim.work_lost_s", sim.work_lost_s)
    rows.add("sim.total_usd", cost.total)
    # rescheduling overhead the paper's cost model ignores:
    ideal = sum(sizes) * 1e-3
    rows.add("sim.reschedule_overhead_frac",
             (sim.gpu_active_s - ideal) / ideal)
    return rows


if __name__ == "__main__":
    enable_compile_cache()
    main()
