"""Paper Table I: DiskANN index-construction time breakdown.

Claims validated: shard index build dominates partition + merge, and its
share grows with (R, L).
"""

from repro.common.compile_cache import enable_compile_cache
from repro.configs.base import IndexConfig
from repro.core.builder import build_diskann

from benchmarks.common import Rows, dataset


def main() -> Rows:
    rows = Rows("table1_breakdown")
    ds = dataset("sift_small")
    for (r, l) in ((8, 16), (16, 32)):
        cfg = IndexConfig(n_clusters=4, degree=r, build_degree=l,
                          block_size=512)
        # reference=True: Table I characterizes the paper's *CPU* DiskANN
        # build; the repo's default (batched, engine-backed) Vamana would
        # shrink the build share the claim is about
        res = build_diskann(ds.data, cfg, n_workers=1, reference=True)
        tag = f"R{r}_L{l}"
        rows.add(f"{tag}.partition_s", res.partition_s)
        rows.add(f"{tag}.build_s", res.build_only_s)
        rows.add(f"{tag}.merge_s", res.merge_s)
        share = res.build_only_s / res.overall_s
        rows.add(f"{tag}.build_share", share)
    shares = [float(v) for k, v in rows.rows if k.endswith("build_share")]
    rows.add("claim.build_dominates", shares[0] > 0.5)
    rows.add("claim.share_grows_with_degree", shares[1] >= shares[0] - 0.05)
    return rows


if __name__ == "__main__":
    enable_compile_cache()
    main()
