"""Paper Table IV + Fig 3: selectivity ε sweep.

Claims: replica proportion shrinks with ε; build-only time shrinks
near-linearly with the replicated-set size; search quality (recall at fixed
budget / distance computations at fixed recall) is maintained or improved.
"""

import dataclasses

from repro.common.compile_cache import enable_compile_cache
from repro.configs.base import IndexConfig
from repro.core.builder import build_scalegann
from repro.data.synthetic import recall_at
from repro.search import search

from benchmarks.common import Rows, dataset


def main() -> Rows:
    rows = Rows("table4_selectivity")
    ds = dataset("deep_analog")
    base = IndexConfig(n_clusters=6, degree=16, build_degree=32,
                       block_size=768)
    results = {}
    for eps in (1.1, 1.2, 1.5, None):  # None → uniform DiskANN replication
        if eps is None:
            res = build_scalegann(ds.data, base, n_workers=2,
                                  selective=False)
            tag = "original"
        else:
            res = build_scalegann(
                ds.data, dataclasses.replace(base, epsilon=eps), n_workers=2
            )
            tag = f"eps{eps}"
        ids, st = search(res.index, ds.queries, 10, data=ds.data, width=96)
        results[tag] = dict(
            proportion=res.stats["replica_proportion"],
            overall_s=res.overall_s,
            build_only_s=res.build_only_s,
            ndist=res.n_distance_computations,
            recall=recall_at(ids, ds.gt, 10),
            search_ndist=st.n_distance_computations / len(ds.queries),
        )
        for k, v in results[tag].items():
            rows.add(f"{tag}.{k}", v)
    props = [results[t]["proportion"] for t in ("eps1.1", "eps1.2", "eps1.5",
                                                "original")]
    rows.add("claim.proportion_monotone",
             all(a <= b + 1e-9 for a, b in zip(props, props[1:])))
    rows.add("claim.build_work_shrinks",
             results["eps1.1"]["ndist"] < results["original"]["ndist"])
    rows.add("claim.recall_maintained",
             results["eps1.1"]["recall"] >= results["original"]["recall"]
             - 0.05)
    # near-linear: build distance-comps track total assignment count
    lin = (results["eps1.1"]["ndist"] / results["original"]["ndist"])
    size_ratio = (1 + results["eps1.1"]["proportion"]) / (
        1 + results["original"]["proportion"])
    rows.add("nearlinear.ndist_ratio", lin)
    rows.add("nearlinear.size_ratio", size_ratio)
    return rows


if __name__ == "__main__":
    enable_compile_cache()
    main()
