"""The benchmark configurations' shapes at test size, and the float64
reference the shape tests hold the program to.

``deep-f32`` (D = 96, float32, L2), ``bigann-u8`` (D = 128, uint8, L2) and
``text2image-ip`` (D = 200, float32, inner product): their widths, dtypes
and metrics over a seeded Gaussian mixture, cut to N = 4,000 vectors in 4
shards with the benchmark's index (R = 64, L = 128).
"""

import functools

import numpy as np

from repro.configs.base import IndexConfig
from repro.core import builder
from repro.data.synthetic import make_clustered

# name: (D, dtype, metric)
SHAPES = {
    "deep-f32": (96, "float32", "l2"),
    "bigann-u8": (128, "uint8", "l2"),
    "text2image-ip": (200, "float32", "ip"),
}
N = 4000
K = 10


def config(name: str, omega: int = 2) -> IndexConfig:
    return IndexConfig(n_clusters=4, block_size=1024, omega=omega,
                       metric=SHAPES[name][2])


@functools.cache
def dataset(name: str):
    d, dtype, metric = SHAPES[name]
    return make_clustered(N, d, n_queries=100, dtype=dtype, metric=metric,
                          seed=0)


@functools.cache
def build(name: str, omega: int = 2):
    return builder.build_scalegann(dataset(name).data, config(name, omega))


# recall@10 floors: those test_search_backends.py holds each topology to
FLOOR = {"merged": 0.85, "routed": 0.95}


def search_kwargs(name: str, topology: str) -> dict:
    """Search knobs at the benchmark's width of 256.  The merged graph is
    seeded as the merged cell seeds it (256 rows).  A probed shard gets 64
    seeds, since a shard of a clustered base falls apart into pieces too
    (ROADMAP D13); it is routed ``"auto"``, except under ``"ip"``, where
    routing by centroid misses shards that hold true neighbours (ROADMAP
    D12) and every shard is probed."""
    if topology == "merged":
        return dict(width=256, n_entries=256)
    routed = "auto" if SHAPES[name][2] == "l2" else len(build(name).shards)
    return dict(width=256, n_entries=64, nprobe=routed)


@functools.cache
def topology_of(name: str, topology: str):
    """One topology object per shape and topology, so that its quantized
    views are made once."""
    b, data = build(name), dataset(name).data
    return b.topology(data) if topology == "merged" else b.shard_topology(data)


def dist64(a: np.ndarray, b: np.ndarray, metric: str) -> np.ndarray:
    """[len(a), len(b)] float64 distances by the metric: squared L2, or
    ``−a·b``."""
    a = np.asarray(a, np.float64)
    b = np.asarray(b, np.float64)
    dots = a @ b.T
    if metric == "ip":
        return -dots
    return (a * a).sum(1)[:, None] + (b * b).sum(1)[None, :] - 2.0 * dots


def slack(a: np.ndarray, b: np.ndarray, metric: str) -> np.ndarray:
    """[len(a), 1] room for float32 rounding in a distance the program
    computes (``‖a‖² + ‖b‖² − 2a·b`` or ``a·b``, summed in float32):
    1e-5 of the row's scale, about 80 float32 epsilons."""
    na = np.linalg.norm(np.asarray(a, np.float64), axis=1)
    nb = np.linalg.norm(np.asarray(b, np.float64), axis=1).max()
    scale = na**2 + nb**2 if metric == "l2" else na * nb
    return 1e-5 * scale[:, None]


def assert_distinct_rows(ids: np.ndarray) -> None:
    s = np.sort(ids, axis=1)
    assert (s[:, 1:] != s[:, :-1]).all(), "an id repeats within a row"


def assert_guarantee(name: str, ids: np.ndarray, floor: float) -> None:
    """The benchmark's ``guarantees`` line for answers to the shape's
    queries: k distinct base ids each, ascending in exact distance by the
    metric (up to :func:`slack`), at recall@10 ≥ ``floor``.  A hit is an
    answer no farther than the query's true 10th neighbour, so ties at the
    10th count either way."""
    ds = dataset(name)
    metric = SHAPES[name][2]
    d64 = dist64(ds.queries, ds.data, metric)
    assert ids.shape == (len(ds.queries), K)
    assert ids.min() >= 0 and ids.max() < N, "not a base id"
    assert_distinct_rows(ids)
    got = np.take_along_axis(d64, ids, axis=1)
    room = slack(ds.queries, ds.data, metric)
    assert (np.diff(got, axis=1) >= -room).all(), "answers out of order"
    kth = np.sort(d64, axis=1)[:, K - 1 : K]
    recall = (got <= kth).mean()
    assert recall >= floor, f"recall@10 {recall:.4f} under {floor}"
