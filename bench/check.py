"""The comparison that decides a run's ``correct``.

Every answer the run judges is a list of ``k`` ids for one query.  Against
the plain reference (``bench/reference.py``) four numbers are compared, each
with its own limit; the run is correct when every number is at or under its
limit:

- ``unanswered``: requests due in the window that never got an answer
  (failed, rejected or never resolved).  Limit 0.
- ``invalid``: answers that are not ``k`` distinct ids of base vectors.
  Limit 0.
- ``order_gap``: the widest amount by which a returned id's exact distance
  exceeds that of the id returned after it, over a scale of the query's.
  The configuration states that answers come in ascending exact distance; a
  lower precision than the one it states breaks that order on near ties.
  Limit from the configuration's ``checks``.
- ``recall_miss``: 1 − recall@k over all judged answers, where an id counts
  when its exact distance is at most the true ``k``-th nearest distance (the
  big-ann-benchmarks definition, ties included).  Limit 1 − the
  configuration's stated recall target.

Distances and scales follow the configuration's ``metric``:

- ``"l2"``: the distance is squared L2, and the scale of ``order_gap`` the
  query's true ``k``-th nearest distance.
- ``"ip"``: the distance is ``−q·x``, which can be negative, so it is no
  scale.  ``order_gap``'s scale is ``‖q‖·‖x_(k)‖``, with ``x_(k)`` the
  query's true ``k``-th nearest: positive, and a bound on ``|q·x|`` there,
  so that float32 rounding of the dots reads in units of ``eps·√D``.

A run that builds also compares the graphs it built:

- ``nn_gap``: the first entry of a row's shard graph list is the nearest
  other row of its shard, since the exact kNN lists it first and detour
  pruning keeps it first.  The widest amount by which its exact distance
  exceeds that of the reference's nearest (:func:`reference.nearest_other`),
  over every row of every shard; infinite for a row whose entry is missing
  or not in its shard.  The excess is taken over the nearest's distance
  (``"l2"``), or over ``‖x_i‖·‖x_nn‖`` (``"ip"``).  A kNN computed below the
  configuration's stated precision picks a near tie on some rows.

Exact distances of returned ids are recomputed in float64 on the host.
"""

from __future__ import annotations

import numpy as np

from bench.reference import dist64, nearest_other, norms64

TINY = np.finfo(np.float64).tiny


def compare(data: np.ndarray, queries: np.ndarray, rows: np.ndarray,
            ids: np.ndarray, gt_ids: np.ndarray, n_unanswered: int,
            limits: dict, k: int, metric: str = "l2") -> tuple[dict, float]:
    """Judge answers ``ids [M, k]`` to ``queries[rows]``.

    ``gt_ids [P, k]`` are the reference's nearest ids for every query of
    the pool.  Returns ``(checks, recall)`` where ``checks`` maps each number
    compared to ``{"value", "limit"}``.
    """
    n = len(data)
    rows = np.asarray(rows, np.int64)
    ids = np.asarray(ids, np.int64).reshape(len(rows), -1)
    m = len(rows)
    ok = np.ones(m, bool)
    if ids.shape[1] != k:
        ok[:] = False
    else:
        ok &= ((ids >= 0) & (ids < n)).all(axis=1)
        srt = np.sort(ids, axis=1)
        ok &= ~(srt[:, 1:] == srt[:, :-1]).any(axis=1)
    gt_d = dist64(data, queries, gt_ids[:, :k], metric)  # [P, k]
    kth = gt_d.max(axis=1)  # [P]
    hits = 0
    gap = 0.0
    if ok.any():
        r = rows[ok]
        d = dist64(data, queries[r], ids[ok], metric)  # [M', k]
        hits = int((d <= kth[r][:, None]).sum())
        if k > 1:
            if metric == "ip":
                far = gt_ids[r, gt_d[r].argmax(axis=1)]  # x_(k) of each
                scale = norms64(queries[r]) * norms64(data[far])
            else:
                scale = kth[r]
            ref = np.maximum(scale, TINY)[:, None]
            gap = float(max(0.0, ((d[:, :-1] - d[:, 1:]) / ref).max()))
    recall = hits / (m * k) if m else 0.0
    checks = {
        "unanswered": {"value": int(n_unanswered), "limit": 0},
        "invalid": {"value": int((~ok).sum()), "limit": 0},
        "order_gap": {"value": gap, "limit": limits["order_gap_max"]},
        "recall_miss": {"value": 1.0 - recall,
                        "limit": 1.0 - limits["recall_at_10_min"]},
    }
    return checks, recall


def nn_gap(data: np.ndarray, graphs: list, metric: str = "l2") -> float:
    """Judge ``graphs``, ``[(ids [n], first [n])]`` per shard: the shard's
    rows as base ids and each row's first graph entry as a base id."""
    gap = 0.0
    for ids, first in graphs:
        ids = np.asarray(ids, np.int64)
        first = np.asarray(first, np.int64)
        if len(ids) < 2:
            continue
        nn = ids[nearest_other(data[ids], metric=metric)]
        if not np.isin(first, ids).all() or (first == ids).any():
            return float("inf")
        q = data[ids]
        d_got = dist64(data, q, first[:, None], metric)[:, 0]
        d_ref = dist64(data, q, nn[:, None], metric)[:, 0]
        scale = (norms64(q) * norms64(data[nn]) if metric == "ip"
                 else d_ref)
        ref = np.maximum(scale, TINY)
        gap = max(gap, float(((d_got - d_ref) / ref).max()))
    return max(gap, 0.0)


def passed(checks: dict) -> bool:
    return all(c["value"] <= c["limit"] for c in checks.values())
