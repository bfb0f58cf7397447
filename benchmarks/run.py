"""Benchmark aggregator: one harness per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--only NAME] [--fast]

Emits ``benchmark,key,value`` CSV lines (claims are ``claim.*`` booleans
that mirror the paper's statements).
"""

from __future__ import annotations

import argparse
import os
import sys
import time
import traceback

from repro.common.compile_cache import enable_compile_cache


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None)
    ap.add_argument("--fast", action="store_true")
    args = ap.parse_args()
    if args.fast:
        os.environ["REPRO_BENCH_FAST"] = "1"

    from benchmarks import (cost_analysis, table1_breakdown,
                            table2_gpu_vs_cpu, table4_selectivity,
                            table5_systems, table6_degree, table7_multigpu)

    suite = {
        "table1_breakdown": table1_breakdown.main,
        "table2_gpu_vs_cpu": table2_gpu_vs_cpu.main,
        "table4_selectivity": table4_selectivity.main,
        "table5_systems": table5_systems.main,
        "table6_degree": table6_degree.main,
        "table7_multigpu": table7_multigpu.main,
        "cost_analysis": cost_analysis.main,
    }
    failures = []
    claims_true = claims_total = 0
    for name, fn in suite.items():
        if args.only and name != args.only:
            continue
        print(f"# ===== {name} =====", flush=True)
        t0 = time.perf_counter()
        try:
            rows = fn()
            for k, v in rows.rows:
                if k.startswith("claim."):
                    claims_total += 1
                    claims_true += v == "True"
        except Exception:
            traceback.print_exc()
            failures.append(name)
        print(f"# {name} took {time.perf_counter() - t0:.1f}s", flush=True)
    print(f"# SUMMARY: {claims_true}/{claims_total} paper claims hold; "
          f"{len(failures)} harness failures {failures or ''}")
    return 1 if failures else 0


if __name__ == "__main__":
    enable_compile_cache()
    sys.exit(main())
