"""Vamana graph build — the DiskANN baseline (paper §II-A, compared §VI).

The paper compares against CPU-based DiskANN end-to-end: uniform ≥1-replica
partitioning + per-shard Vamana build + merge.  We implement Vamana
faithfully (Subramanya et al. 2019):

  1. start from a random regular graph of degree R;
  2. for each point p (two passes, α=1 then α>1): greedy-search the current
     graph for p, collect the visited set V, and set N(p) = RobustPrune(p, V,
     α, R); add reverse edges p→q for q ∈ N(p), re-pruning q when it
     overflows R.

Two implementations share that schedule:

  * :func:`build_shard_index_vamana` (the default) runs **batched insertion
    rounds** — the GPU graph-indexing recipe (CAGRA/GANNS-style): each round
    greedy-searches a whole batch of points at once through the
    ``repro.search`` engine (:func:`repro.search.beam_pool`; ``jax``
    backend by default, ``numpy`` as the exact fallback), prunes the whole
    batch with a vectorized masked-α-domination :func:`robust_prune_batch`,
    and applies the reverse edges grouped by destination (scatter into free
    slots, batched re-prune for rows that overflow R).  Points inside one
    round search the same graph snapshot — the standard batched-build
    approximation; recall parity with the sequential build is tested to
    within 0.01.
  * :func:`build_shard_index_vamana_sequential` is the paper-faithful
    one-point-at-a-time reference (python greedy search + per-point
    RobustPrune) — the seed-loop baseline ``bench_build.py`` measures the
    batched speedup against, and the oracle the parity tests compare to.

The distance hot loop is the same kernel the ScaleGANN build uses — on the
paper's CPUs this is the stage that dominates (Table I) and the reason the
GPU offload wins.  ``build_shard_index_vamana`` is a drop-in alternative to
``cagra.build_shard_index`` so the framework's "integrates with any indexing
algorithm" claim (§VIII) is demonstrated, and Table IV's "applying this
approach to DiskANN's Vamana index, the conclusion still holds" run is
reproducible.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Callable, Optional

import numpy as np

from repro.configs.base import IndexConfig
from repro.core.cagra import ShardIndex


@dataclasses.dataclass
class VamanaRoundState:
    """Snapshot handed to ``round_hook`` after every completed insertion
    round — the natural checkpoint grain of the batched build.

    A round is a pure function of (graph, batch, data), and the batch
    schedule is derived deterministically from ``seed``, so this snapshot
    is everything a bit-compatible resume needs: restore ``graph`` and the
    ``(pass_idx, next_start)`` cursor and the remaining rounds replay
    exactly (asserted by tests/test_fleet.py).  ``graph`` is a copy of the
    real rows (padding excluded) — the hook may keep or serialize it.
    """

    round_idx: int  # completed rounds so far, across both α passes
    n_rounds_total: int
    pass_idx: int  # which α pass (0: α=1 pass, 1: α pass)
    next_start: int  # batch offset the *next* round would start at
    graph: np.ndarray  # [n, R] int64 copy
    n_distance_computations: int
    n: int = 0
    R: int = 0


def require_l2(cfg: IndexConfig) -> None:
    """Refuse a metric the Vamana build does not compute: its searches,
    RobustPrune and reverse edges are squared L2 (ROADMAP B2 is open for
    Vamana), so any other ``cfg.metric`` would build an L2 graph without
    saying so."""
    if cfg.metric != "l2":
        raise ValueError(
            f"the Vamana build is L2 only (ROADMAP B2); got metric="
            f"{cfg.metric!r}: build inner-product indexes with algo='cagra'")


def _dists(data: np.ndarray, ids: np.ndarray, p: np.ndarray) -> np.ndarray:
    rows = data[ids].astype(np.float32)
    d = rows - p[None, :]
    return np.einsum("nd,nd->n", d, d)


def robust_prune(
    p_id: int,
    cand: np.ndarray,
    cand_d: np.ndarray,
    data: np.ndarray,
    alpha: float,
    R: int,
    counter: list,
) -> np.ndarray:
    """RobustPrune(p, V, α, R): repeatedly keep the closest candidate p*, and
    drop every candidate v with α·d(p*, v) <= d(p, v) (occluded by p*)."""
    keep_ids: list[int] = []
    order = np.argsort(cand_d, kind="stable")
    cand = cand[order]
    cand_d = cand_d[order]
    alive = np.ones(len(cand), bool)
    alive &= cand != p_id
    while alive.any() and len(keep_ids) < R:
        i = int(np.argmax(alive))  # first alive == closest alive
        v = int(cand[i])
        keep_ids.append(v)
        alive[i] = False
        if not alive.any():
            break
        rest = np.nonzero(alive)[0]
        d_vs = _dists(data, cand[rest], data[v].astype(np.float32))
        counter[0] += len(rest)
        occluded = alpha * d_vs <= cand_d[rest]
        alive[rest[occluded]] = False
    return np.asarray(keep_ids, np.int64)


def robust_prune_batch(
    p_ids: np.ndarray,  # [B] point ids being pruned
    cand: np.ndarray,  # [B, C] candidate ids (-1 = pad)
    cand_d: np.ndarray,  # [B, C] d(p, candidate) (inf = pad)
    data: np.ndarray,
    alpha: float,
    R: int,
    counter: list,
    vecs: np.ndarray | None = None,
) -> np.ndarray:
    """Vectorized RobustPrune over a batch of points: ``[B, R]`` kept ids
    (-1 padded, compacted to the front of each row).

    ``vecs`` — optional pre-gathered ``[B, C, D]`` candidate vectors
    aligned with ``cand`` (callers that already materialized the block,
    like the reverse-edge overflow path, pass it to avoid a second
    scattered gather of the same rows); reordered here to the sorted
    candidate order.

    Per row the algorithm — and its tie-breaks — is exactly
    :func:`robust_prune`: candidates sort by (distance, input position),
    each of up to R selection steps keeps the closest alive candidate p*
    and kills every alive v with ``α·d(p*, v) <= d(p, v)``.  The selection
    loop runs R times with every step batched: one ``[B, C, D]`` gather up
    front, then one masked ``[B, C]`` distance tile per step.  Masked
    (dead/padding) lanes are computed but **not counted** — the same
    convention as the routed search driver's padded lanes
    (``run_split``/``n_real``) — so ``counter`` advances exactly as the
    sequential prune's per-row ``len(rest)`` would.
    """
    cand = np.asarray(cand, np.int64)
    cand_d = np.asarray(cand_d, np.float32)
    p_ids = np.asarray(p_ids, np.int64)
    nb, c = cand.shape
    invalid = (cand < 0) | (cand == p_ids[:, None]) | ~np.isfinite(cand_d)
    d_key = np.where(invalid, np.inf, cand_d)
    order = np.argsort(d_key, axis=1, kind="stable")
    sid = np.take_along_axis(cand, order, axis=1)
    sd = np.take_along_axis(d_key, order, axis=1)
    alive = np.isfinite(sd)
    if vecs is None:
        vecs = np.asarray(
            data[np.maximum(sid, 0).reshape(-1)], np.float32
        ).reshape(nb, c, -1)
    else:
        vecs = np.take_along_axis(
            np.asarray(vecs, np.float32), order[:, :, None], axis=1
        )
    keep = np.full((nb, R), -1, np.int64)
    rows = np.arange(nb)
    for t in range(R):
        if not alive.any():
            break
        i = np.argmax(alive, axis=1)  # first alive == closest alive
        active = alive[rows, i]  # rows with anything left to keep
        keep[active, t] = sid[rows, i][active]
        alive[rows, i] = False
        n_rest = int(alive.sum())
        counter[0] += n_rest
        if n_rest == 0:
            continue
        pv = vecs[rows, i]  # [B, D] the step's p* vectors
        diff = vecs - pv[:, None, :]
        d_vs = np.einsum("bcd,bcd->bc", diff, diff)
        occluded = (alpha * d_vs <= sd) & alive & active[:, None]
        alive[occluded] = False
    return keep


def _greedy_search_visited(
    data: np.ndarray,
    graph: np.ndarray,
    entry: int,
    q: np.ndarray,
    L: int,
    counter: list,
) -> tuple[np.ndarray, np.ndarray]:
    """GreedySearch returning the visited (expanded) set and its distances."""
    visited: dict[int, float] = {}
    d0 = float(_dists(data, np.asarray([entry]), q)[0])
    counter[0] += 1
    cand = {int(entry): d0}
    expanded: set[int] = set()
    while True:
        un = [(d, v) for v, d in cand.items() if v not in expanded]
        if not un:
            break
        un.sort()
        d, v = un[0]
        expanded.add(v)
        visited[v] = d
        nbrs = graph[v]
        nbrs = nbrs[nbrs >= 0]
        fresh = [u for u in nbrs.tolist() if u not in cand]
        if fresh:
            ds = _dists(data, np.asarray(fresh), q)
            counter[0] += len(fresh)
            for u, du in zip(fresh, ds.tolist()):
                cand[u] = du
        if len(cand) > L:  # keep closest L
            keep = sorted(cand.items(), key=lambda kv: kv[1])[:L]
            cand = dict(keep)
    ids = np.asarray(list(visited.keys()), np.int64)
    return ids, np.asarray([visited[int(i)] for i in ids], np.float32)


def _random_regular_init(
    n: int, R: int, rng: np.random.Generator
) -> np.ndarray:
    """Vectorized random start graph: one ``[n, R]`` integer draw with the
    self-loop shift (a row may repeat a neighbor — harmless: searches dedup
    by visited set and both passes overwrite every row)."""
    if n <= 1:
        return np.full((n, R), -1, np.int64)
    graph = rng.integers(0, n - 1, size=(n, R))
    graph[graph >= np.arange(n)[:, None]] += 1
    return graph.astype(np.int64)


def _apply_reverse_edges(
    batch: np.ndarray,  # [B] the just-(re)pruned point ids
    pruned: np.ndarray,  # [B, R] their new neighbor lists (-1 pad)
    graph: np.ndarray,  # [n, R] mutated in place
    data: np.ndarray,
    alpha: float,
    R: int,
    counter: list,
) -> None:
    """Grouped reverse-edge update: every q ∈ pruned[b] gains the edge
    q → batch[b].  New sources are grouped by destination with one stable
    sort; destinations with free capacity take a single fancy-indexed
    scatter (rows stay compacted: valid entries first), destinations that
    would overflow R are re-pruned in one :func:`robust_prune_batch` call
    over ``row ∪ new sources`` — the batched equivalent of the sequential
    per-edge "insert or re-prune"."""
    src_p = np.repeat(batch, pruned.shape[1])
    dst_q = pruned.reshape(-1)
    ok = dst_q >= 0
    src_p, dst_q = src_p[ok], dst_q[ok]
    if dst_q.size == 0:
        return
    # skip pairs already present (sequential: `if p in row: continue`)
    present = (graph[dst_q] == src_p[:, None]).any(axis=1)
    src_p, dst_q = src_p[~present], dst_q[~present]
    if dst_q.size == 0:
        return
    o = np.argsort(dst_q, kind="stable")
    qs, ps = dst_q[o], src_p[o]
    uq, start = np.unique(qs, return_index=True)
    cnt_new = np.diff(np.append(start, len(qs)))
    seg = np.repeat(np.arange(len(uq)), cnt_new)
    rank = np.arange(len(qs)) - start[seg]
    fill = (graph[uq] >= 0).sum(axis=1)  # rows are kept compacted
    fits = fill + cnt_new <= R

    # in-capacity destinations: scatter new sources into the free tail
    m_fit = fits[seg]
    if m_fit.any():
        graph[qs[m_fit], fill[seg[m_fit]] + rank[m_fit]] = ps[m_fit]

    # overflowing destinations: batched re-prune over row ∪ new sources
    n_ovf = int((~fits).sum())
    if n_ovf == 0:
        return
    ovf = uq[~fits]
    max_new = int(cnt_new[~fits].max())
    cand = np.full((n_ovf, R + max_new), -1, np.int64)
    cand[:, :R] = graph[ovf]
    ovf_pos = np.full(len(uq), -1, np.int64)
    ovf_pos[~fits] = np.arange(n_ovf)
    m_ovf = ~m_fit
    cand[ovf_pos[seg[m_ovf]], R + rank[m_ovf]] = ps[m_ovf]
    valid = cand >= 0
    cvecs = np.asarray(
        data[np.maximum(cand, 0).reshape(-1)], np.float32
    ).reshape(n_ovf, cand.shape[1], -1)
    diff = cvecs - np.asarray(data[ovf], np.float32)[:, None, :]
    cand_d = np.where(
        valid, np.einsum("bcd,bcd->bc", diff, diff), np.inf
    ).astype(np.float32)
    counter[0] += int(valid.sum())  # scoring q against its candidates
    pruned_q = robust_prune_batch(ovf, cand, cand_d, data, alpha, R, counter,
                                  vecs=cvecs)
    graph[ovf] = -1
    graph[ovf, : pruned_q.shape[1]] = pruned_q


DEFAULT_BUILD_BATCH = 256


def build_shard_index_vamana(
    vectors: np.ndarray,
    cfg: IndexConfig,
    *,
    alpha: float = 1.2,
    seed: int = 0,
    backend: str = "jax",
    batch_size: int | None = None,
    pad_to: int | None = None,
    round_hook: Optional[Callable[[VamanaRoundState], None]] = None,
    resume: object | None = None,
) -> ShardIndex:
    """Batched Vamana build of one shard (degree R = cfg.degree, search
    width L = cfg.build_degree).

    Each insertion round greedy-searches a whole batch of points through
    the ``repro.search`` engine (:func:`~repro.search.beam_pool` on
    ``backend`` — ``"jax"`` for throughput, ``"numpy"`` for the exact
    reference semantics), then applies a vectorized RobustPrune and grouped
    reverse-edge updates; the two-pass (α=1, then α) schedule is the
    paper's.

    Jit-shape discipline (the repo's serving lesson applies to builds too):
    round batches are always exactly ``batch_size`` queries (the last round
    cycles real points, excluded from stats via ``n_real``), and ``pad_to``
    pads the shard's rows so *different shards share one trace* — the
    builder passes the size of its largest shard, making a multi-shard
    build pay the ``jax`` trace once instead of once per distinct shard
    size.  Padding rows are all ``-1`` in the graph, so the beam can never
    reach them; they cost O(pad) memset per round, not distance work.

    Preemption/checkpoint surface (the spot-fleet story, paper §IV):
    ``round_hook`` fires after every completed round with a
    :class:`VamanaRoundState` snapshot; a hook that raises aborts the build
    at the round boundary (``repro.fleet`` raises
    :class:`~repro.fleet.Preempted` carrying the saved checkpoint).
    ``resume`` is any object with ``pass_idx`` / ``next_start`` / ``graph``
    / ``n_distance_computations`` attributes (a ``VamanaRoundState`` or a
    ``repro.fleet.ShardCheckpoint``): the build restores the graph and the
    round cursor and continues **bit-compatibly** — the resumed build's
    final graph is identical to an uninterrupted one because the batch
    schedule is replayed from ``seed`` and each round is deterministic in
    (graph, batch, data).  Resume must use the same ``seed`` /
    ``batch_size`` / ``alpha`` as the original build (checked where the
    checkpoint records them).
    """
    require_l2(cfg)
    data = np.asarray(vectors, np.float32)
    n = len(data)
    R = min(cfg.degree, max(1, n - 1))
    if n <= 1:
        # degenerate shard — tombstone consolidation and shard-split can
        # hand the builder empty or single-point shards; there is no medoid
        # to argmin and no round to run (an empty batch would also break
        # the np.resize shape-stabilizer), so the graph is trivially edgeless
        return ShardIndex(
            graph=np.full((n, R), -1, np.int32), n_distance_computations=0
        )
    L = cfg.build_degree
    rng = np.random.default_rng(seed)
    counter = [0]
    n_pad = max(n, pad_to or n)
    store = data
    if n_pad > n:
        store = np.zeros((n_pad, data.shape[1]), np.float32)
        store[:n] = data
    graph = np.full((n_pad, R), -1, np.int64)
    graph[:n] = _random_regular_init(n, R, rng)
    medoid = int(((data - data.mean(0)) ** 2).sum(1).argmin())
    order = rng.permutation(n)
    nb = batch_size or DEFAULT_BUILD_BATCH
    pool = max(L, R + 1)  # the visited pool RobustPrune consumes
    rounds_per_pass = max(1, math.ceil(n / nb))
    n_rounds_total = 2 * rounds_per_pass

    start_pass, start_off = 0, 0
    if resume is not None:
        ck_n = getattr(resume, "n", n) or n
        ck_r = getattr(resume, "R", R) or R
        if ck_n != n or ck_r != R:
            raise ValueError(
                f"resume checkpoint shape mismatch: checkpoint n={ck_n} "
                f"R={ck_r} vs build n={n} R={R}"
            )
        graph[:n] = np.asarray(resume.graph, np.int64)
        counter[0] = int(resume.n_distance_computations)
        start_pass = int(resume.pass_idx)
        start_off = int(resume.next_start)
        if start_off >= n:  # checkpoint taken at a pass boundary
            start_pass += 1
            start_off = 0

    from repro.search import beam_pool  # deferred: keeps core import-light
    from repro.telemetry import current_tracer

    tr = current_tracer()  # no-op tracer: one branch per round, no clocks
    for pi, a in enumerate((1.0, alpha)):  # two passes per the paper
        if pi < start_pass:
            continue
        s0 = start_off if pi == start_pass else 0
        for s in range(s0, n, nb):
            if tr.enabled:
                t_round0 = tr.now()
                dc0 = counter[0]
            batch = order[s : s + nb]
            m = len(batch)
            rows = np.resize(batch, nb)  # cycle real points: stable shapes
            # expansion budget = pool size: a bounded best-first search
            # saturates its candidate list after ~pool expansions, and the
            # engine's serving default (width + width//2) spends the extra
            # margin on straggler lanes the build does not need — recall
            # parity with the sequential build holds at the tighter budget
            # (tested), at ~2× less beam time per round
            pool_ids, pool_d, p_stats = beam_pool(
                store, graph, medoid, data[rows], pool,
                backend=backend, metric="l2", n_iters=pool,
                n_real=m if m < nb else None,
            )
            counter[0] += p_stats.n_distance_computations
            pruned = robust_prune_batch(
                batch, pool_ids[:m], pool_d[:m], data, a, R, counter
            )
            graph[batch] = -1
            graph[batch, : pruned.shape[1]] = pruned
            _apply_reverse_edges(
                batch, pruned, graph, data, a, R, counter
            )
            ridx = pi * rounds_per_pass + (s // nb) + 1
            if tr.enabled:
                # emitted before the hook: a hook-raised preemption must
                # not erase a round that did complete (its track — hence
                # its nesting under the fleet attempt span — comes from
                # the enclosing span stack on this thread)
                tr.complete(
                    "vamana.round", t_round0, tr.now(), round=ridx,
                    of=n_rounds_total, pass_idx=pi,
                    dist=counter[0] - dc0, hops=int(p_stats.n_hops),
                )
            if round_hook is not None:
                round_hook(VamanaRoundState(
                    round_idx=ridx,
                    n_rounds_total=n_rounds_total,
                    pass_idx=pi,
                    next_start=s + nb,
                    graph=graph[:n].copy(),
                    n_distance_computations=counter[0],
                    n=n,
                    R=R,
                ))
    return ShardIndex(
        graph=graph[:n].astype(np.int32), n_distance_computations=counter[0]
    )


def build_shard_index_vamana_sequential(
    vectors: np.ndarray, cfg: IndexConfig, *, alpha: float = 1.2,
    seed: int = 0,
) -> ShardIndex:
    """Sequential (paper-faithful) Vamana build of one shard — the
    one-point-at-a-time CPU algorithm, kept as the seed-loop baseline the
    batched build is benched and parity-tested against."""
    require_l2(cfg)
    data = np.asarray(vectors, np.float32)
    n = len(data)
    R = min(cfg.degree, max(1, n - 1))
    if n <= 1:  # degenerate shard: same early return as the batched build
        return ShardIndex(
            graph=np.full((n, R), -1, np.int32), n_distance_computations=0
        )
    L = cfg.build_degree
    rng = np.random.default_rng(seed)
    counter = [0]
    # random R-regular start
    graph = np.full((n, R), -1, np.int64)
    for i in range(n):
        choices = rng.choice(n - 1, size=min(R, n - 1), replace=False)
        choices[choices >= i] += 1
        graph[i, : len(choices)] = choices
    medoid = int(((data - data.mean(0)) ** 2).sum(1).argmin())
    order = rng.permutation(n)
    for a in (1.0, alpha):  # two passes per the paper
        for p in order:
            vis, vis_d = _greedy_search_visited(
                data, graph, medoid, data[p], L, counter
            )
            pruned = robust_prune(int(p), vis, vis_d, data, a, R, counter)
            graph[p, :] = -1
            graph[p, : len(pruned)] = pruned
            # reverse edges with overflow re-prune
            for q in pruned:
                row = graph[q]
                if int(p) in row:
                    continue
                slot = np.nonzero(row < 0)[0]
                if slot.size:
                    graph[q, slot[0]] = p
                else:
                    cand = np.concatenate([row, [p]])
                    cd = _dists(data, cand, data[q].astype(np.float32))
                    counter[0] += len(cand)
                    pq = robust_prune(int(q), cand, cd, data, a, R, counter)
                    graph[q, :] = -1
                    graph[q, : len(pq)] = pq
    return ShardIndex(
        graph=graph.astype(np.int32), n_distance_computations=counter[0]
    )
