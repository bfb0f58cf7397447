"""Paper Table VI: build-degree sweep on the high-dimensional dataset.

Claims: overall construction time grows with (R, L) for every system, and
ScaleGANN keeps its ≤ ~2× replication overhead vs Extended CAGRA across
degrees (the accelerator advantage grows with degree — distance computation
share rises).
"""

import dataclasses

from repro.common.compile_cache import enable_compile_cache
from repro.configs.base import IndexConfig
from repro.core import builder

from benchmarks.common import Rows, dataset


def main() -> Rows:
    rows = Rows("table6_degree")
    ds = dataset("laion_analog")
    base = IndexConfig(n_clusters=5, block_size=768)
    overall = {}
    for (r, l) in ((8, 16), (16, 32), (32, 64)):
        cfg = dataclasses.replace(base, degree=r, build_degree=l)
        sg = builder.build_scalegann(ds.data, cfg, n_workers=2)
        ec = builder.build_extended_cagra(ds.data, cfg, n_workers=2)
        tag = f"R{r}_L{l}"
        overall[(r, "sg")] = sg.overall_s
        overall[(r, "ec")] = ec.overall_s
        rows.add(f"{tag}.scalegann_overall_s", sg.overall_s)
        rows.add(f"{tag}.extended_cagra_overall_s", ec.overall_s)
        rows.add(f"{tag}.sg_over_ec_build_only",
                 sg.build_only_s / max(ec.build_only_s, 1e-9))
    rows.add("claim.time_grows_with_degree",
             overall[(8, "sg")] < overall[(32, "sg")])
    return rows


if __name__ == "__main__":
    enable_compile_cache()
    main()
