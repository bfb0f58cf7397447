"""Under ``"l2"`` the judge computes what it computed before it took a
metric: the reference's ids and distances, the float64 distances, the
checks and ``nn_gap``, bit for bit, for both configurations.

``_old_*`` below are that judge's functions as they were, kept verbatim
(the L2 arithmetic alone), so that the comparison runs on whatever CPU
runs the tests."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import check, common, control, data, reference

SEED = 2**31 + 11
TINY = np.finfo(np.float64).tiny


@functools.partial(jax.jit, static_argnames=("k", "precision"))
def _old_block_knn(q, x, k: int, precision: str):
    dot, split, bf16 = reference._dot, reference._split, reference._bf16
    if precision == "bfloat16":
        q, x = bf16(q), bf16(x)
        dots = dot(q.astype(jnp.bfloat16), x.astype(jnp.bfloat16))
    elif precision == "high":
        (qh, ql), (xh, xl) = split(q), split(x)
        dots = dot(qh, xh) + dot(qh, xl) + dot(ql, xh)
    else:
        dots = dot(q, x)
    d = (jnp.sum(q * q, axis=1, keepdims=True)
         + jnp.sum(x * x, axis=1)[None, :] - 2.0 * dots)
    neg, idx = jax.lax.top_k(-d, k)
    return -neg, idx


def _old_exact_knn(data, queries, k, precision="highest"):
    data, queries = np.asarray(data), np.asarray(queries)
    if precision == "int4":
        data, queries = data >> 4, queries >> 4
    x = jnp.asarray(data.astype(np.float32))
    nq = len(queries)
    pad = (-nq) % reference.BLOCK
    qs = np.concatenate([queries.astype(np.float32),
                         np.zeros((pad, queries.shape[1]), np.float32)])
    dists, ids = [], []
    for s in range(0, len(qs), reference.BLOCK):
        d, i = _old_block_knn(jnp.asarray(qs[s:s + reference.BLOCK]), x, k,
                              precision)
        dists.append(np.asarray(d))
        ids.append(np.asarray(i))
    return (np.concatenate(dists)[:nq],
            np.concatenate(ids)[:nq].astype(np.int64))


def _old_nearest_other(vectors, precision="highest"):
    _, ids = _old_exact_knn(vectors, vectors, min(2, len(vectors)),
                            precision)
    self_first = ids[:, 0] == np.arange(len(vectors))
    return np.where(self_first, ids[:, 1], ids[:, 0])


def _old_sq_dist64(data, queries, ids, block=4096):
    out = np.empty(ids.shape, np.float64)
    for s in range(0, len(ids), block):
        rows = np.asarray(data[ids[s:s + block]], np.float64)
        q = np.asarray(queries[s:s + block], np.float64)[:, None, :]
        out[s:s + block] = ((rows - q) ** 2).sum(axis=-1)
    return out


def _old_compare(data, queries, rows, ids, gt_ids, limits, k):
    """``compare`` as it was, for valid answers and nothing unanswered."""
    kth = _old_sq_dist64(data, queries, gt_ids[:, :k]).max(axis=1)
    d = _old_sq_dist64(data, queries[rows], ids)
    ref = np.maximum(kth[rows], TINY)[:, None]
    hits = int((d <= kth[rows][:, None]).sum())
    gap = float(max(0.0, ((d[:, :-1] - d[:, 1:]) / ref).max()))
    recall = hits / (len(rows) * k)
    return {"unanswered": {"value": 0, "limit": 0},
            "invalid": {"value": 0, "limit": 0},
            "order_gap": {"value": gap, "limit": limits["order_gap_max"]},
            "recall_miss": {"value": 1.0 - recall,
                            "limit": 1.0 - limits["recall_at_10_min"]}}, recall


def _old_nn_gap(data, graphs):
    gap = 0.0
    for ids, first in graphs:
        nn = ids[_old_nearest_other(data[ids])]
        q = data[ids]
        d_got = _old_sq_dist64(data, q, first[:, None])[:, 0]
        d_ref = _old_sq_dist64(data, q, nn[:, None])[:, 0]
        ref = np.maximum(d_ref, TINY)
        gap = max(gap, float(((d_got - d_ref) / ref).max()))
    return max(gap, 0.0)


@pytest.mark.parametrize("name", ["deep-f32", "bigann-u8"])
def test_l2_judge_is_bit_identical(name):
    cfg = dict(common.config(name), n=20000)
    assert cfg["metric"] == "l2"
    k, limits = cfg["k"], cfg["checks"]
    dep = data.deployment(cfg)
    x = dep.data
    pool = data.queries(dep, 1000, SEED, cfg.get("queries"))
    got = {}
    for prec in ("highest", control.CONTROL[cfg["dtype"]]):
        d, ids = reference.exact_knn(x, pool, k, precision=prec,
                                     metric=cfg["metric"])
        old_d, old_ids = _old_exact_knn(x, pool, k, prec)
        np.testing.assert_array_equal(d, old_d)
        np.testing.assert_array_equal(ids, old_ids)
        np.testing.assert_array_equal(
            reference.dist64(x, pool, ids, cfg["metric"]),
            _old_sq_dist64(x, pool, ids))
        got[prec] = ids
    gt, ctl = got["highest"], got[control.CONTROL[cfg["dtype"]]]
    rows = np.arange(len(pool))[::-1]  # answers in another order than gt
    checks, recall = check.compare(x, pool, rows, ctl[rows], gt, 0, limits,
                                   k, cfg["metric"])
    assert (checks, recall) == _old_compare(x, pool, rows, ctl[rows], gt,
                                            limits, k)
    assert checks["order_gap"]["value"] > 0  # the control's inversions
    shards = [np.arange(s, min(s + 6000, len(x)), dtype=np.int64)
              for s in range(0, len(x), 5000)]  # overlapping, as replicas
    graphs = []
    for ids in shards:
        nn = reference.nearest_other(x[ids], metric=cfg["metric"])
        np.testing.assert_array_equal(nn, _old_nearest_other(x[ids]))
        # first neighbours at the control's precision: a gap to judge
        graphs.append((ids, ids[_old_nearest_other(x[ids], "high")]))
    assert check.nn_gap(x, graphs, cfg["metric"]) == _old_nn_gap(x, graphs)
