"""The kNN kernel's work count against a hand count."""

from bench import common
from bench.work import knn


def test_knn_work_by_hand():
    ops, nbytes = knn.work([3, 4], dim=2, k=2)
    # 2·n²·D per shard: 2·9·2 + 2·16·2
    assert ops == 36 + 64
    # per shard: read it twice (queries and points) in f32, write n × k
    # f32 distances and int32 ids
    assert nbytes == (2 * 3 * 2 * 4 + 3 * 2 * 8) + (2 * 4 * 2 * 4 + 4 * 2 * 8)


def test_k_beyond_the_shard_is_clipped():
    assert knn.work([2], dim=1, k=129)[1] == 2 * 2 * 4 + 2 * 2 * 8


def test_bound_follows_intensity():
    peak = common.load_json(common.BENCH / "peaks.json")["devices"][
        "TPU v5 lite"]
    t, bound = knn.least_seconds(*knn.work([4], dim=2, k=2), peak)
    assert bound == "memory" and t > 0
    ops, nbytes = knn.work([8000], dim=96, k=129)
    t, bound = knn.least_seconds(ops, nbytes, peak)
    assert bound == "compute" and t == ops / peak["bf16_flops_per_s"]
