"""Shard-index merge into one global graph (paper §IV step 3, §V-C).

Replicated vectors appear in multiple shards; their per-shard neighbor lists
are *unioned* (DiskANN's merge) and the result is degree-capped to R keeping
the closest neighbors.  The merge is the only stage that touches every shard
index, so it is written as a streaming pass over (graph, manifest) pairs:

  * **Order invariance** — parallel assignment makes intra-shard vector order
    non-deterministic (§V-C).  DiskANN's sequential-read merge breaks there;
    the paper adds a disk *buffer-state check*.  We reproduce the property
    with explicit (local → global) manifests: every edge is translated
    through the manifest, so merge output is a pure function of the edge
    *set*, never of row order.  ``tests/test_merge.py`` asserts permutation
    invariance.
  * **Buffered sequential reads** — ``BufferedShardReader`` mirrors the
    paper's buffered disk path: rows are fetched through a block buffer; a
    *state check* detects when the requested global id is outside the
    buffered window and refills (random access degenerates gracefully,
    sequential access hits the buffer).
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.cagra import ShardIndex
from repro.core.partition import Shard
from repro.telemetry import NULL_SPAN, current_tracer


def entry_points(center: int, n_vectors: int, n: int = 16,
                 cover: np.ndarray | None = None) -> np.ndarray:
    """``center`` + ``n`` rows, as sorted unique row ids: the first
    ``n // 2`` rows of ``cover`` at most, and the rest spread evenly over
    ``[0, n_vectors)``.

    ``cover`` holds one row of each graph component that ``center`` does
    not belong to, largest component first (:attr:`GlobalIndex.component_roots`):
    a beam never leaves the component it was seeded in, so a component
    with no seed is never searched, and evenly spaced rows miss the
    small ones.

    Always exactly ``min(n + 1, n_vectors)`` seeds: the center regularly
    collides with one of the ``linspace`` samples, and before the
    deterministic top-up below a collision silently shrank the seed set —
    replicas agreed with each other but not with the documented contract,
    and searches seeded one entry short.  A fixed count also keeps the seed
    array one shape for the jitted beams."""
    want = min(n + 1, n_vectors)
    cover = (np.empty(0, np.int64) if cover is None
             else np.asarray(cover, np.int64)[: n // 2])
    seeds = np.unique(np.concatenate([
        [center], cover,
        np.linspace(0, n_vectors - 1, n - len(cover), dtype=np.int64),
    ]))
    if len(seeds) < want:
        # top up with the smallest ids not already chosen — ids in
        # [0, want + len(seeds)) suffice by pigeonhole, so the scan
        # stays O(n), not O(n_vectors)
        fresh = np.setdiff1d(
            np.arange(min(want + len(seeds), n_vectors), dtype=np.int64),
            seeds, assume_unique=True,
        )
        seeds = np.unique(np.concatenate([seeds, fresh[: want - len(seeds)]]))
    return seeds


@dataclasses.dataclass
class GlobalIndex:
    """Merged graph over the full dataset, global coordinates, -1 padded."""

    graph: np.ndarray  # [N, R] int32
    medoid: int  # DiskANN-style single entry point
    n_vectors: int

    def entry_points(self, n: int = 16) -> np.ndarray:
        """Medoid + a stratified sample — CAGRA-style multi-entry seeds (a
        merged kNN graph has only local edges; multiple entries restore
        navigability; deterministic so serving replicas agree).  Where
        the sample leaves a component of the graph without a seed, each
        other component gets one (:attr:`component_roots`).  See
        :func:`entry_points`."""
        seeds = entry_points(self.medoid, self.n_vectors, n)
        label, roots = self._components
        if len(roots) == 0 or len(np.unique(label[seeds])) == len(roots) + 1:
            return seeds
        return entry_points(self.medoid, self.n_vectors, n, roots)

    @property
    def component_roots(self) -> np.ndarray:
        """The smallest row id of every weakly connected component of the
        graph but the medoid's, largest component first (ties by that id).

        A merged graph of well-separated clusters falls apart into
        components that no edge leads out of."""
        return self._components[1]

    @functools.cached_property
    def _components(self) -> tuple[np.ndarray, np.ndarray]:
        """Each row's component label, and :attr:`component_roots`.  Found
        once, on first use; the graph is not changed after the merge."""
        from scipy.sparse import csr_matrix
        from scipy.sparse.csgraph import connected_components

        n = self.n_vectors
        if n == 0:
            return np.empty(0, np.int64), np.empty(0, np.int64)
        ok = self.graph >= 0
        nbr = self.graph[ok]  # row-major: each row's edges in turn
        indptr = np.concatenate([[0], np.cumsum(ok.sum(axis=1))])
        adj = csr_matrix((np.ones(len(nbr), np.int8), nbr, indptr),
                         shape=(n, n))
        _, label = connected_components(adj, directed=True,
                                        connection="weak")
        size = np.bincount(label)
        # np.unique's first index of each label is its smallest row id
        lab, root = np.unique(label, return_index=True)
        keep = lab != label[self.medoid]
        lab, root = lab[keep], root[keep]
        return label, root[np.lexsort((root, -size[lab]))]

    @property
    def degree(self) -> int:
        return self.graph.shape[1]

    def out_degrees(self) -> np.ndarray:
        return (self.graph >= 0).sum(axis=1)


class BufferedShardReader:
    """Sequential-friendly buffered reader with the paper's state check.

    Wraps a [n, D] shard-data array (or memmap).  ``get(local_id)`` serves
    from an in-memory block buffer; if the id misses the buffered window
    (out-of-order read), the buffer is refilled — correctness is preserved
    for *any* order, efficiency for sorted order.  ``hits``/``misses``
    expose buffer efficiency to the tests/benchmarks.
    """

    def __init__(self, rows: np.ndarray, buffer_rows: int = 4096):
        self._rows = rows
        self._buf_rows = int(buffer_rows)
        self._lo = 0
        self._hi = 0
        self._buf: np.ndarray | None = None
        self.hits = 0
        self.misses = 0

    def get(self, local_id: int) -> np.ndarray:
        # --- buffer state check (paper §V-C) ---
        if self._buf is None or not (self._lo <= local_id < self._hi):
            self.misses += 1
            self._lo = local_id
            self._hi = min(local_id + self._buf_rows, len(self._rows))
            self._buf = np.asarray(self._rows[self._lo : self._hi])
        else:
            self.hits += 1
        return self._buf[local_id - self._lo]


def _translate(graph: np.ndarray, ids: np.ndarray) -> np.ndarray:
    """Local neighbor ids -> global ids; -1 stays -1."""
    safe = np.maximum(graph, 0)
    out = ids[safe].astype(np.int64)
    out[graph < 0] = -1
    return out


def _edge_list(
    shards: list[Shard], indexes: list[ShardIndex]
) -> tuple[np.ndarray, np.ndarray]:
    """Flatten every shard graph into one global ``(gid, neighbor)`` edge
    list, in shard → row → slot order (the order the sequential scatter
    appended edges in, which is what "first seen" means downstream).
    Self-loops and -1 pads are dropped."""
    gid_parts, nbr_parts = [], []
    for shard, idx in zip(shards, indexes):
        g = _translate(idx.graph, shard.ids)  # [n, R] global
        gid_parts.append(
            np.repeat(shard.ids.astype(np.int64), g.shape[1])
        )
        nbr_parts.append(g.reshape(-1))
    gids = np.concatenate(gid_parts) if gid_parts else np.empty(0, np.int64)
    nbrs = np.concatenate(nbr_parts) if nbr_parts else np.empty(0, np.int64)
    ok = (nbrs >= 0) & (nbrs != gids)
    return gids[ok], nbrs[ok]


def _segment_distances(
    data: np.ndarray, gids: np.ndarray, nbrs: np.ndarray, metric: str,
    block: int = 1 << 18,
) -> np.ndarray:
    """The index metric's distance between each edge's endpoints: squared
    L2, or ``−x_g·x_n`` under ``"ip"`` (what the shard kNN ranked the edges
    by), gathered and summed in numpy.  The host path of
    :func:`_edge_distances`, for data too large to hold on the device.
    Blocked so the gather never materializes more than ``2 · block · D``
    f32 elements (``data`` may be a memmap at the 10^5+ scale)."""
    if metric not in ("l2", "ip"):
        raise ValueError(f"unknown metric {metric!r}")
    d = np.empty(len(gids), np.float32)
    for s in range(0, len(gids), block):
        sl = slice(s, s + block)
        a = np.asarray(data[nbrs[sl]], np.float32)
        b = np.asarray(data[gids[sl]], np.float32)
        if metric == "ip":
            d[sl] = -np.einsum("ed,ed->e", a, b)
        else:
            diff = a - b
            d[sl] = np.einsum("ed,ed->e", diff, diff)
    return d


_EDGE_BLOCK = 1 << 16  # edges per device block: one compiled shape


def _device_bytes_limit() -> int | None:
    """The default device's memory in bytes; ``None`` where the backend
    reports none (the CPU)."""
    return (jax.devices()[0].memory_stats() or {}).get("bytes_limit")


def _fits_on_device(data: np.ndarray) -> bool:
    """Whether the float32 copy of ``data`` fits in half of the device's
    memory, with row ids that int32 holds."""
    n, dim = data.shape
    limit = _device_bytes_limit()
    return n < 2**31 and (limit is None or 4 * n * dim <= limit // 2)


@functools.partial(jax.jit, static_argnames=("metric",))
def _block_distances(x: jax.Array, gids: jax.Array, nbrs: jax.Array,
                     metric: str) -> jax.Array:
    """:func:`_segment_distances` of one block of edges, on the device:
    float32 elementwise products summed over D (no dot, which a TPU runs
    as one bfloat16 pass at default precision)."""
    a = x[nbrs].astype(jnp.float32)
    b = x[gids].astype(jnp.float32)
    if metric == "ip":
        return -jnp.sum(a * b, axis=-1)
    diff = a - b
    return jnp.sum(diff * diff, axis=-1)


def _edge_distances(
    data: np.ndarray, gids: np.ndarray, nbrs: np.ndarray, metric: str,
) -> tuple[np.ndarray, bool, int]:
    """:func:`_segment_distances` of every edge, and the path that ran:
    ``(d, resident, h2d_bytes)``.

    Where :func:`_fits_on_device`, ``data`` is uploaded once in its own
    dtype (f64 as f32) and each block of :data:`_EDGE_BLOCK` edges uploads
    its int32 endpoint ids; the last block is padded with row 0 and its
    padding dropped.  Every block is dispatched before ``d`` is read back.
    Nothing stays on the device after the call.  Otherwise the numpy path
    runs and uploads nothing.  The two paths sum in another order, so a
    distance may differ in its last bits."""
    if metric not in ("l2", "ip"):
        raise ValueError(f"unknown metric {metric!r}")
    if not _fits_on_device(data):
        return _segment_distances(data, gids, nbrs, metric), False, 0
    host = np.asarray(data)
    if host.dtype.itemsize > 4:
        host = host.astype(np.float32)
    x = jnp.asarray(host)
    n, blk = len(gids), _EDGE_BLOCK
    pad = -n % blk
    g = np.pad(gids.astype(np.int32), (0, pad)).reshape(-1, blk)
    e = np.pad(nbrs.astype(np.int32), (0, pad)).reshape(-1, blk)
    out = [_block_distances(x, gb, eb, metric) for gb, eb in zip(g, e)]
    d = np.concatenate(jax.device_get(out))[:n]
    return d, True, host.nbytes + g.nbytes + e.nbytes


def _order_key(d: np.ndarray) -> np.ndarray:
    """int64 keys in ``[0, 2^32)`` that order as the float32 values ``d``,
    negatives included: the bit pattern of a negative is flipped whole,
    that of a non-negative gets its sign bit set.  ``−0`` is first made
    ``+0``, so that equal values give equal keys."""
    u = (d + np.float32(0.0)).view(np.uint32)
    return np.where(u >> 31, ~u, u | np.uint32(1 << 31)).astype(np.int64)


def _union_dedup_cap(
    shards: list[Shard],
    indexes: list[ShardIndex],
    n_total: int,
    degree: int,
    data: np.ndarray | None,
    metric: str,
) -> np.ndarray:
    """Vectorized edge-union: one global ``(gid, neighbor)`` sort with
    segment-wise dedup and degree cap — replaces the per-gid python loop
    (passes 2–3 of the sequential merge).

    Per-gid semantics match the loop version: duplicate ``(gid, neighbor)``
    pairs collapse to their first appearance; the cap keeps the ``degree``
    closest neighbors by ``metric`` when ``data`` is given (ties broken by
    first-seen order, the loop's stable ``argsort`` behavior) and the
    first-seen ``degree`` otherwise.  The output is a pure function of the edge *set*,
    so the permutation-invariance contract (§V-C) is preserved — only the
    within-row order of an under-capacity ``data`` row differs from the
    loop (distance-sorted instead of first-seen; same id set).

    The distances are computed on the device where the float32 copy of
    ``data`` fits in half of its memory, else in numpy
    (:func:`_edge_distances`); a pair of candidates within a few float32
    ulps of each other at the cap may swap between the two paths.

    Under an enabled tracer its stages are the spans ``merge.edges``,
    ``merge.sort``, ``merge.distances`` and ``merge.cap``;
    ``merge.distances`` carries ``resident`` (the device path ran),
    ``n_edges`` (deduplicated edges) and ``h2d_bytes`` (uploaded).
    """
    tr = current_tracer()
    graph = np.full((n_total, degree), -1, np.int32)
    with tr.span("merge.edges") if tr.enabled else NULL_SPAN:
        gids, nbrs = _edge_list(shards, indexes)
    if gids.size == 0:
        return graph
    with tr.span("merge.sort") if tr.enabled else NULL_SPAN:
        # dedup: stable (gid, nbr) sort keeps the earliest appended copy
        # first (one int64 key sorts like lexsort((nbrs, gids)), in half
        # the time)
        order = np.argsort(gids * n_total + nbrs, kind="stable")
        sg, sn = gids[order], nbrs[order]
        first = np.ones(len(sg), bool)
        first[1:] = (sg[1:] != sg[:-1]) | (sn[1:] != sn[:-1])
        ug, un, upos = sg[first], sn[first], order[first]
    # cap: order each gid's unique neighbors by (distance, first-seen) or
    # (first-seen) alone, then keep ranks < degree.  A stable sort of the
    # first-seen order by an int64 (gid, distance) key sorts like
    # lexsort((upos, d, ug)): _order_key orders signed f32 as values.
    if data is not None:
        with tr.span("merge.distances") if tr.enabled else NULL_SPAN as sp:
            d, resident, h2d = _edge_distances(data, ug, un, metric)
            sp.set(resident=resident, n_edges=len(ug), h2d_bytes=h2d)
            key = (ug << 32) | _order_key(d)
    else:
        key = ug
    with tr.span("merge.cap") if tr.enabled else NULL_SPAN:
        by_pos = np.argsort(upos)
        sel = by_pos[np.argsort(key[by_pos], kind="stable")]
        g2, n2 = ug[sel], un[sel]
        idx = np.arange(len(sel))
        seg_start = np.ones(len(sel), bool)
        seg_start[1:] = g2[1:] != g2[:-1]
        rank = idx - np.maximum.accumulate(np.where(seg_start, idx, 0))
        keep = rank < degree
        graph[g2[keep], rank[keep]] = n2[keep].astype(np.int32)
    return graph


def _union_dedup_cap_loop(
    shards: list[Shard],
    indexes: list[ShardIndex],
    n_total: int,
    degree: int,
    data: np.ndarray | None,
    metric: str,
) -> np.ndarray:
    """Seed-loop reference for passes 2–3 (presized union buffers + one
    python iteration per global id) — kept for the merge parity tests and
    the ``bench_build.py`` seed-loop baseline.  Its distances stay numpy
    on either platform, so it is independent of the device path."""
    # Pass 1: count edges per global id to presize the union buffers.
    counts = np.zeros(n_total, np.int64)
    for shard, idx in zip(shards, indexes):
        valid = (idx.graph >= 0).sum(axis=1)
        np.add.at(counts, shard.ids, valid)
    slots = np.maximum(counts, 1)
    offsets = np.zeros(n_total + 1, np.int64)
    np.cumsum(slots, out=offsets[1:])
    edge_buf = np.full(offsets[-1], -1, np.int64)
    fill = np.zeros(n_total, np.int64)

    # Pass 2: translate + scatter each shard's edges (order-free).
    for shard, idx in zip(shards, indexes):
        g = _translate(idx.graph, shard.ids)  # [n, R] global
        for row, gid in enumerate(shard.ids):
            nbrs = g[row]
            nbrs = nbrs[nbrs >= 0]
            s = offsets[gid] + fill[gid]
            edge_buf[s : s + len(nbrs)] = nbrs
            fill[gid] += len(nbrs)

    # Pass 3: dedup + cap per vector.
    graph = np.full((n_total, degree), -1, np.int32)
    for gid in range(n_total):
        nbrs = edge_buf[offsets[gid] : offsets[gid] + fill[gid]]
        nbrs = nbrs[(nbrs >= 0) & (nbrs != gid)]
        if nbrs.size == 0:
            continue
        # stable unique preserving first-seen order
        uniq, first = np.unique(nbrs, return_index=True)
        uniq = uniq[np.argsort(first, kind="stable")]
        if uniq.size > degree:
            if data is not None:
                v = np.asarray(data[gid], np.float32)
                cand = np.asarray(data[uniq], np.float32)
                d = (-(cand * v).sum(axis=1) if metric == "ip"
                     else ((cand - v) ** 2).sum(axis=1))
                uniq = uniq[np.argsort(d, kind="stable")[:degree]]
            else:
                uniq = uniq[:degree]
        graph[gid, : uniq.size] = uniq
    return graph


def merge_shard_indexes(
    shards: list[Shard],
    indexes: list[ShardIndex],
    n_total: int,
    degree: int,
    *,
    data: np.ndarray | None = None,
    centroid_of: np.ndarray | None = None,
    reference: bool = False,
    metric: str = "l2",
) -> GlobalIndex:
    """Edge-union merge with degree cap.

    For each global vector, collect the union of its neighbor lists over all
    shards containing it.  Cap at ``degree``: if ``data`` is given, keep the
    *closest* neighbors by the index's ``metric`` (squared L2, or the
    largest inner products under ``"ip"``; DiskANN behavior); otherwise keep
    shard order (replicas append after originals).

    ``centroid_of`` ([N] shard id of the original assignment) is only used
    for the medoid choice; the medoid is the vector closest to the global
    mean when ``data`` is given, else vector 0.

    ``reference=True`` runs the original per-gid python loop (passes 2–3)
    instead of the vectorized global segment sort — the seed-loop baseline
    ``bench_build.py`` compares against.
    """
    if len(shards) != len(indexes):
        raise ValueError("shards and indexes must align")
    union = _union_dedup_cap_loop if reference else _union_dedup_cap
    graph = union(shards, indexes, n_total, degree, data, metric)

    medoid = 0
    if data is not None:
        # one stratified gather serves both the mean and the medoid probe
        probe_ids = np.linspace(0, n_total - 1, min(n_total, 8192)).astype(int)
        probe = np.asarray(data[probe_ids], np.float32)
        mean = probe.mean(axis=0)
        medoid = int(probe_ids[((probe - mean) ** 2).sum(axis=1).argmin()])
    return GlobalIndex(graph=graph, medoid=medoid, n_vectors=n_total)


def connectivity_stats(index: GlobalIndex, *, sample: int = 2048, seed: int = 0):
    """BFS reachability from the medoid over a sampled frontier — the merge's
    raison d'être is global connectivity (§IV), so we measure it."""
    n = index.n_vectors
    seen = np.zeros(n, bool)
    frontier = [index.medoid]
    seen[index.medoid] = True
    while frontier:
        nxt = index.graph[frontier].reshape(-1)
        nxt = nxt[nxt >= 0]
        nxt = np.unique(nxt)
        nxt = nxt[~seen[nxt]]
        seen[nxt] = True
        frontier = nxt.tolist()
    degs = index.out_degrees()
    return {
        "reachable_fraction": float(seen.mean()),
        "mean_degree": float(degs.mean()),
        "min_degree": int(degs.min()),
        "isolated": int((degs == 0).sum()),
    }
