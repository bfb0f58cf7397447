"""Work of the build's exact kNN kernel (``knn_pallas``), from the shard sizes
a build reports.

The count is that of exact brute-force kNN of each shard against itself:
``2·n²·D`` operations (one multiply-add per coordinate of every pair) and
the bytes of reading the shard once as queries and once as points, in
float32, and writing ``n × k`` distances (f32) and ids (int32).  Padded rows
and columns the kernel also computes are not counted, so the share errs low.
It counts this algorithm's work: a build that replaces brute force with
another kNN algorithm needs the benchmark re-pointed to that algorithm's
count.
"""

from __future__ import annotations


def work(shard_sizes, dim: int, k: int) -> tuple[float, float]:
    """``(operations, bytes)`` of exact kNN over each shard of the build."""
    ops = 0.0
    nbytes = 0.0
    for n in shard_sizes:
        ops += 2.0 * n * n * dim
        nbytes += 2.0 * n * dim * 4 + n * min(k, n) * 8
    return ops, nbytes


def least_seconds(ops: float, nbytes: float, peak: dict) -> tuple[float, str]:
    """The least time the chip could take and which bound sets it.

    The kernel's dot products run at ``Precision.HIGHEST``, which a TPU
    executes as several bfloat16 passes; dividing by the published bfloat16
    peak credits it with one pass, so the share reads low and cannot pass
    100%."""
    t_ops = ops / peak["bf16_flops_per_s"]
    t_bytes = nbytes / peak["hbm_bytes_per_s"]
    return (t_ops, "compute") if t_ops >= t_bytes else (t_bytes, "memory")
