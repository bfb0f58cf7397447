"""The control of a cell's comparison, run apart from the benchmark's runs.

    python3 bench/control.py --workload <cell> --seeds 1,2,3 [--precision p]

The plain reference takes the program's place and answers the queries a run
of the cell judges, computed in the nearest precision below the one the
configuration states (``CONTROL``: three-pass bfloat16 products for float32
at ``Precision.HIGHEST``, four-bit codes for uint8).  Its answers go through
the same comparison as the program's (``bench/check.py``); a sound
comparison finds the control not correct.  For a cell that builds, the
reference also takes the place of the shard builds' exact kNN: each row's
first neighbour is its shard's nearest other row at the control's precision,
over the program's own partition.  One JSON line per seed.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (os.path.join(_ROOT, "src"), _ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import numpy as np  # noqa: E402

from bench import check, common, data, reference  # noqa: E402

CONTROL = {"float32": "high", "uint8": "int4"}


def readings(cell: str, seed: int, precision: str | None = None, *,
             overrides: dict | None = None) -> dict:
    wl = common.workload(cell)
    cfg = common.config(wl["config"])
    for key, v in (overrides or {}).items():
        cfg = dict(cfg, **{key: v})
    k = cfg["k"]
    metric = cfg["metric"]
    dep = data.deployment(cfg)
    pool = data.queries(dep, cfg["assumed"]["query_pool"], seed,
                        cfg.get("queries"))
    n = wl["params"].get("queries", len(pool))
    prec = precision or CONTROL[cfg["dtype"]]
    _, gt = reference.exact_knn(dep.data, pool, k, metric=metric)
    _, ids = reference.exact_knn(dep.data, pool[:n], k, precision=prec,
                                 metric=metric)
    checks, recall = check.compare(dep.data, pool, np.arange(n), ids, gt, 0,
                                   cfg["checks"], k, metric)
    if wl["driver"] == "build_steps":
        checks["nn_gap"] = {
            "value": check.nn_gap(dep.data, _graphs(dep.data, cfg, prec),
                                  metric),
            "limit": cfg["checks"]["nn_gap_max"]}
    return {"workload": cell, "seed": seed, "precision": prec,
            "correct": check.passed(checks), "recall_at_10": recall,
            "checks": checks}


def _graphs(data: np.ndarray, cfg: dict, precision: str) -> list:
    from repro.configs.base import IndexConfig
    from repro.core.partition import partition

    out = []
    for shard in partition(data, IndexConfig(**cfg["index"])).shards:
        ids = np.asarray(shard.ids, np.int64)
        out.append((ids, ids[reference.nearest_other(
            data[ids], precision=precision, metric=cfg["metric"])]))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--precision", choices=reference.PRECISIONS)
    args = ap.parse_args(argv)
    for s in args.seeds.split(","):
        print(json.dumps(readings(args.workload, int(s), args.precision)),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
