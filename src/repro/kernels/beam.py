"""Fused beam-search engine: one dispatch per served batch.

The serving hot loop of every system the paper compares (§VI-A2) is the
same bounded best-first graph traversal.  The old ``pallas`` backend ran it
as *interpret-mode validation*: every beam step round-tripped candidate
lists and visited bitmaps through HBM/host.  This module is the
device-resident engine — the BANG/PilotANN layout on TPU terms:

  * **One ``pallas_call`` per batch.**  The grid walks queries; each
    program runs the *whole* traversal for its query inside the kernel —
    candidate list, running top-k and the visited-tag bitmap live in
    **VMEM scratch across all beam iterations** (``lax.while_loop`` with
    per-trip early exit), never touching HBM until the final top-k write.
  * **Seed ids ride the scalar-prefetch channel.**  The entry points are
    the ``PrefetchScalarGridSpec`` operand: they land in SMEM before the
    kernel body runs, so seeding reads scalars instead of streaming a
    block, and the graph/vector blocks for the first hop are already being
    fetched while the seeds score.
  * **One score row per query, then pure on-chip traversal.**  The
    query's score row over the whole resident shard comes first (f32/bf16:
    one ``[Q, D]×[D, N]`` XLA matmul per batch, the same op the XLA
    lowering precomputes, each program reading its row; uint8: the
    **int8-native MXU** path of :func:`repro.kernels.distance._u8_code_dots`
    in the kernel — codes recentered into int8, int8×int8→int32
    ``dot_general``).  Every per-trip neighbor score is then a VMEM gather,
    done as a one-hot bf16 matmul (Mosaic has no vector gather) over
    ``[expand·R, COLUMN_CHUNK]`` column tiles, exact through a bf16 split
    of the gathered values (:func:`_gather_exact`).  Resident in VMEM are
    the graph, the ``[1, N]`` rows (norms, visited tags, score rows) and,
    for uint8, the codes and the exact re-rank rows; compiled for v5e at
    D = 128, R = 64 that is up to 184,319 vectors for f32/bf16 and 86,015
    for uint8 with re-rank (:func:`pallas_vmem_bytes` against
    :data:`VMEM_LIMIT_BYTES`).  A larger shard raises; streaming it from
    HBM is future work.
  * **Fused exact re-rank epilogue.**  For staged dtypes the kernel ends
    by re-scoring its top ``kq`` candidates against the resident f32
    vectors and sorting by ``(distance, id)`` — a served batch never
    returns to host between traversal and re-rank.
  * **Sorting is the bitonic network** (:func:`~repro.kernels.topk
    .bitonic_sort_lex`) keyed on ``(distance, position)`` — ``lax.top_k``'s
    exact tie rule — carrying candidate ids and expanded flags as payloads
    through each compare-exchange.

Off-TPU the same algorithm lowers to a **flat-batch XLA** path (default
when no TPU is attached): the per-query visited tags flatten to one
``[Q·(N+1)]`` array so the scatter/gather pair runs unbatched (CPU XLA's
vmapped scatter is the measured bottleneck of the jax backend), and for
small panels the per-trip scoring reads a precomputed ``[Q, N]`` dot tile
(one sgemm per batch).  Both lowerings reproduce the ``jax`` backend's
traversal *bit-for-bit* on ids and stats — same wavefront selection, same
visited-tag dedup (last duplicate wins), same ``(value, position)`` tie
rules — which the interpret-mode parity suite pins.

Semantics are defined by ``repro.search.jax_backend._batch_beam``; this
module only changes where the state lives.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.distance import _u8_code_dots
from repro.kernels.topk import _next_pow2, bitonic_sort_lex

LANE = 128
DEFAULT_EXPAND = 8
# the flat-batch XLA lowering precomputes the [Q, N+1] query×shard dot tile
# (one sgemm per batch, per-trip scoring becomes pure gathers) when the tile
# stays under this many elements; bigger panels score gathered rows per trip
PRECOMPUTE_TILE_LIMIT = 4 * 1024 * 1024
# shard columns per one-hot tile in the Pallas kernel's per-trip gathers
COLUMN_CHUNK = 2048

# scoped-VMEM limit the Pallas kernel compiles under (v5e has 128 MiB of
# VMEM; the compiler's default scope is 16 MiB).  It is also the bound
# :func:`pallas_vmem_bytes` is held to.  Compiled for a v5e chip at
# D = 128, R = 64, the model stays above the compiler's scoped allocation
# (532-551 bytes a column for f32 near the limit): the f32 stage compiles
# at N = 184319 (the model's largest) and is refused at N = 192511
# (106.0 MB); uint8 with re-rank compiles at N = 86015 (the model's
# largest) and at 88063, and is refused at N = 98304.
VMEM_LIMIT_BYTES = 100 * 1024 * 1024

_I32_MAX = jnp.iinfo(jnp.int32).max
# f32 dots run at full precision in the XLA lowering and in the Pallas
# wrapper's batch matmul: a TPU's default f32 matmul rounds operands to
# bf16, which would make the "exact" f32 stage approximate
_EXACT = jax.lax.Precision.HIGHEST


def _round_up(v: int, m: int) -> int:
    return -(-v // m) * m


def _columns(n: int) -> tuple[int, int]:
    """(chunk, Np): one-hot tile width and the padded column count (N + 1
    with the spill column, a multiple of the chunk)."""
    chunk = min(COLUMN_CHUNK, _round_up(n + 1, LANE))
    return chunk, _round_up(n + 1, chunk)


def pallas_vmem_bytes(n: int, d: int, r: int, x_dtype, *,
                      dx: int | None = None) -> int:
    """VMEM the Pallas lowering holds for an ``[n, d]`` shard of degree
    ``r``: the lane-padded graph, the uint8 code panel (the float stages
    read a precomputed score row instead), the exact re-rank rows when
    ``dx`` is given, and 40 bytes a column for the ``[1, Np]`` rows (norms,
    code sums and the query's score row, double-buffered; visited tags and
    two score rows; rounded up), plus 2 MiB for what does not grow with
    ``n``: the ``[expand·r, chunk]`` one-hot tiles and the candidate
    state."""
    _, np_cols = _columns(n)
    row = _round_up(r, LANE) * 4 + 40
    if jnp.dtype(x_dtype) == jnp.uint8:
        row += _round_up(d, LANE)
    if dx is not None:
        row += _round_up(dx, LANE) * 4
    return np_cols * row + (2 << 20)


def _pad_lanes(a: jax.Array, width: int, value) -> jax.Array:
    """Right-pad a ``[1, w]`` row to ``width`` lanes with ``value`` (no
    zero-width piece: Mosaic refuses empty vectors)."""
    if a.shape[1] == width:
        return a
    return jnp.concatenate(
        [a, jnp.full((1, width - a.shape[1]), value, a.dtype)], axis=1)


# ---------------------------------------------------------------------------
# Flat-batch XLA lowering (CPU/GPU serving path; bit-identical to jax
# backend)
# ---------------------------------------------------------------------------


@functools.partial(
    jax.jit,
    static_argnames=("k", "width", "n_iters", "expand", "metric",
                     "rerank_k", "precompute"),
)
def _fused_beam_xla(
    x: jax.Array,  # [N, D] storage: f32, bf16, or uint8 affine codes
    graph: jax.Array,  # [N, R] int32
    entries: jax.Array,  # [E] int32 (E <= width)
    queries: jax.Array,  # [Q, D] f32/bf16, or [Q, D] int32 query codes
    scale: jax.Array,  # f32 scalars (uint8 stage; traced, no retrace
    zp: jax.Array,  # per QuantSpec)
    x_exact,  # [N, Dx] f32 | None — fused re-rank storage
    q_exact,  # [Q, Dx] f32 | None
    *,
    k: int,
    width: int,
    n_iters: int,
    expand: int,
    metric: str,
    rerank_k: int | None,
    precompute: bool,
):
    """Whole-batch fused traversal (+ optional exact re-rank) in one jit.

    Returns ``(ids [Q, k_out] i32 with -1, dists [Q, k_out] f32,
    n_dist [Q] i32, hops [Q] i32, n_rerank [Q] i32)`` where
    ``k_out = rerank_k or k``.
    """
    n, d_real = x.shape
    r = graph.shape[1]
    nq = queries.shape[0]
    ne = entries.shape[0]
    n_new = expand * r
    sentinel = jnp.int32(n)
    rows_q = jnp.arange(nq, dtype=jnp.int32)
    base = rows_q * (n + 1)  # flat visited-tag row offsets
    is_u8 = x.dtype == jnp.uint8

    if is_u8:
        # queries arrive as uint8 codes (shared wrapper contract with the
        # Pallas lowering); the int32 code math here is the jax backend's
        queries = queries.astype(jnp.int32)
        xi = x.astype(jnp.int32)
        xi_n = jnp.sum(xi * xi, axis=1)  # [N] code norms
        xi_s = jnp.sum(xi, axis=1)  # [N] code sums (ip)
        cqn = jnp.sum(queries * queries, axis=1, keepdims=True)  # [Q, 1]
        cqs = jnp.sum(queries, axis=1, keepdims=True)

        def score(ids2d):
            """jax-backend uint8 math, batched: int32-accumulated code
            dots + affine correction (bit-exact integers)."""
            safe = jnp.clip(ids2d, 0, n - 1)
            rows = xi[safe.reshape(-1)].reshape(nq, ids2d.shape[1], d_real)
            dots = jax.lax.dot_general(
                queries, rows, (((1,), (2,)), ((0,), (0,))),
                preferred_element_type=jnp.int32,
            )  # [Q, M]
            if metric == "ip":
                return -(scale * scale * dots.astype(jnp.float32)
                         + scale * zp
                         * (cqs + xi_s[safe]).astype(jnp.float32)
                         + d_real * zp * zp)
            d_codes = (xi_n[safe] + cqn - 2 * dots).astype(jnp.float32)
            return jnp.maximum(d_codes, 0.0) * (scale * scale)
    else:
        qf = queries.astype(jnp.float32)
        xf = x.astype(jnp.float32)
        xn = jnp.sum(xf * xf, axis=1)
        if precompute:
            # one sgemm per batch; traversal scoring becomes pure gathers.
            # Same reduction as the gathered-rows dot, so bit-identical.
            wall = jnp.concatenate(
                [jnp.matmul(qf, xf.T, precision=_EXACT),
                 jnp.zeros((nq, 1), jnp.float32)], axis=1
            ).reshape(-1)  # [Q·(N+1)] flat, spill column N
            xn1 = jnp.concatenate([xn, jnp.zeros((1,), jnp.float32)])

            def score(ids2d):
                m = ids2d.shape[1]
                g = (base[:, None] + ids2d).reshape(-1)
                dots = wall[g].reshape(nq, m)
                if metric == "ip":
                    return -dots
                return xn1[ids2d.reshape(-1)].reshape(nq, m) - 2.0 * dots
        else:

            def score(ids2d):
                m = ids2d.shape[1]
                safe = jnp.clip(ids2d, 0, n - 1)
                rows = xf[safe.reshape(-1)].reshape(nq, m, d_real)
                dots = jax.lax.dot_general(
                    qf, rows, (((1,), (2,)), ((0,), (0,))),
                    preferred_element_type=jnp.float32, precision=_EXACT,
                )
                if metric == "ip":
                    return -dots
                return xn[safe] - 2.0 * dots

    # ---- seeding (identical to jax backend, batch-shaped) ----
    pad = width - ne
    seed_ids = jnp.broadcast_to(entries[None, :], (nq, ne))
    cand_ids = jnp.concatenate(
        [seed_ids, jnp.full((nq, pad), sentinel, jnp.int32)], axis=1
    )
    cand_d = jnp.concatenate(
        [score(seed_ids), jnp.full((nq, pad), jnp.inf, jnp.float32)], axis=1
    )
    cand_exp = jnp.concatenate(
        [jnp.zeros((nq, ne), bool), jnp.ones((nq, pad), bool)], axis=1
    )
    # flat visited tags: 0 = never seen, slot n of each row is the spill
    tags = jnp.zeros((nq * (n + 1),), jnp.int32)
    tags = tags.at[(base[:, None] + seed_ids).reshape(-1)].set(1)
    n_dist = jnp.full((nq,), ne, jnp.int32)
    hops = jnp.zeros((nq,), jnp.int32)
    done = jnp.zeros((nq,), bool)

    def cond(state):
        *_, hops_, done_, _it = state
        del _it
        return jnp.any((~done_) & (hops_ < n_iters))

    def body(state):
        ids, ds, exp, tags, n_dist, hops, done, it = state
        masked = jnp.where(exp, jnp.inf, ds)
        neg_sel, sel = jax.lax.top_k(-masked, expand)
        live = jnp.isfinite(neg_sel)  # [Q, expand]
        converged = ~live[:, :1]
        halt = done[:, None] | converged | (hops[:, None] >= n_iters)
        live = live & ~halt
        exp_u = jnp.where(
            halt, exp, exp.at[rows_q[:, None], sel].set(True)
        )
        v = jnp.take_along_axis(ids, sel, axis=1)
        nbrs = graph[jnp.clip(v, 0, n - 1)].reshape(nq, n_new)
        valid = jnp.repeat(live, r, axis=1) & (nbrs >= 0)
        safe = jnp.where(valid, nbrs, sentinel)
        # flat visited gather + tagged scatter + re-gather (duplicate
        # neighbors within a wavefront resolve to the last writer, the
        # same resolution the jax backend's vmapped scatter exhibits)
        gidx = (base[:, None] + safe).reshape(-1)
        seen = (tags[gidx] != 0).reshape(nq, n_new)
        slot = 2 + it * n_new + jnp.arange(n_new, dtype=jnp.int32)[None, :]
        widx = (base[:, None]
                + jnp.where(valid & ~seen, nbrs, sentinel)).reshape(-1)
        tags_u = tags.at[widx].set(
            jnp.broadcast_to(slot, (nq, n_new)).reshape(-1)
        )
        fresh = valid & ~seen & (tags_u[gidx].reshape(nq, n_new) == slot)
        nd = jnp.where(fresh, score(jnp.where(fresh, nbrs, 0)), jnp.inf)
        all_ids = jnp.concatenate(
            [ids, jnp.where(fresh, nbrs, sentinel)], axis=1
        )
        all_d = jnp.concatenate([ds, nd], axis=1)
        all_exp = jnp.concatenate(
            [exp_u, jnp.zeros((nq, n_new), bool)], axis=1
        )
        neg_keep, keep = jax.lax.top_k(-all_d, width)
        new_ids = jnp.where(
            jnp.isfinite(neg_keep),
            jnp.take_along_axis(all_ids, keep, axis=1), sentinel,
        )
        new_exp = jnp.take_along_axis(all_exp, keep, axis=1)
        h = halt[:, 0]
        ids = jnp.where(h[:, None], ids, new_ids)
        ds = jnp.where(h[:, None], ds, -neg_keep)
        exp = jnp.where(h[:, None], exp, new_exp)
        n_dist = n_dist + jnp.where(h, 0, fresh.sum(axis=1)).astype(
            jnp.int32)
        hops = hops + jnp.where(h, 0, live.sum(axis=1)).astype(jnp.int32)
        return (ids, ds, exp, tags_u, n_dist, hops,
                done | converged[:, 0], it + 1)

    state = (cand_ids, cand_d, cand_exp, tags, n_dist, hops, done,
             jnp.int32(0))
    ids, ds, _, _, n_dist, hops, _, _ = jax.lax.while_loop(
        cond, body, state
    )
    neg_top, top = jax.lax.top_k(-ds, k)
    top_ids = jnp.take_along_axis(ids, top, axis=1)
    out_ids = jnp.where(
        jnp.isfinite(neg_top) & (top_ids != sentinel), top_ids, -1
    )
    out_d = jnp.take_along_axis(ds, top, axis=1)
    if metric != "ip" and not is_u8:
        out_d = out_d + jnp.sum(
            queries.astype(jnp.float32) ** 2, axis=1, keepdims=True
        )
    n_rerank = jnp.zeros((nq,), jnp.int32)
    if rerank_k is None:
        return out_ids, out_d, n_dist, hops, n_rerank

    # ---- fused exact-f32 re-rank epilogue (same dispatch) ----
    valid = out_ids >= 0
    rows = x_exact[jnp.clip(out_ids, 0, n - 1).reshape(-1)].reshape(
        nq, k, -1
    )
    if metric == "ip":
        dex = -jnp.einsum("qcd,qd->qc", rows, q_exact, precision=_EXACT)
    else:
        diff = rows - q_exact[:, None, :]
        dex = jnp.sum(diff * diff, axis=-1)
    ids_key = jnp.where(valid, out_ids, _I32_MAX)
    d_key = jnp.where(valid, dex, jnp.inf).astype(jnp.float32)
    order = jnp.lexsort((ids_key, d_key), axis=-1)[:, :rerank_k]
    r_ids = jnp.take_along_axis(ids_key, order, axis=1)
    r_d = jnp.take_along_axis(d_key, order, axis=1)
    r_ids = jnp.where(r_ids == _I32_MAX, -1, r_ids)
    n_rerank = valid.sum(axis=1).astype(jnp.int32)
    return r_ids, r_d, n_dist, hops, n_rerank


# ---------------------------------------------------------------------------
# Pallas kernel lowering (VMEM-resident traversal)
# ---------------------------------------------------------------------------


def _gather_exact(vals, eq, pieces: int):
    """``[1, Np]`` f32 values through an ``[M, Np]`` bf16 one-hot to
    ``[1, M]``, exactly.  Each row of ``eq`` holds one 1, so a single-pass bf16 matmul
    returns the selected bf16 value unchanged; ``vals`` is split into
    ``pieces`` bf16 parts that sum back to it in f32 (3 cover a 24-bit
    significand, 2 an integer below 2**16) and each part is gathered in one
    pass.  This keeps the one-hot operand bf16: a full-precision f32
    matmul would split it too, and spills out of VMEM at shard size."""
    out, rest = None, vals
    for _ in range(pieces):
        part = rest.astype(jnp.bfloat16)
        rest = rest - part.astype(jnp.float32)
        g = jax.lax.dot_general(
            part, eq, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        out = g if out is None else out + g
    return out


def _beam_kernel(
    ent_ref,  # [E] int32 SMEM (scalar-prefetch operand)
    q_ref,  # [1, D] query block (f32 / bf16 / uint8 codes)
    w_ref,  # [1, Np] f32 query·shard dots (f32/bf16 stages; dummy for u8)
    x_ref,  # [Np(-1), D] uint8 codes (u8 stage; dummy otherwise)
    graph_ref,  # [Np(-1), R] int32
    xaux1_ref,  # [1, Np] f32 norms | int32 code norms
    xaux2_ref,  # [1, Np] int32 code sums (uint8 ip; zeros otherwise)
    s_ref,  # (1, 1) SMEM scale
    zp_ref,  # (1, 1) SMEM zero-point
    xex_ref,  # [Np(-1), Dx] f32 exact rows (re-rank) — dummy when unused
    qex_ref,  # [1, Dx] f32 exact query — dummy when unused
    out_ids_ref,  # [1, k_out] int32
    out_d_ref,  # [1, k_out] f32
    out_nd_ref,  # [1, 1] int32
    out_hops_ref,  # [1, 1] int32
    out_nrr_ref,  # [1, 1] int32
    tags_ref,  # VMEM scratch [1, Np] int32 — visited tags
    cd_ref,  # VMEM scratch [1, W] f32 — candidate distances
    ci_ref,  # VMEM scratch [1, W] int32 — candidate ids
    ce_ref,  # VMEM scratch [1, W] int32 — expanded flags
    sc_ref,  # VMEM scratch [2, Np] f32 — the query's score row(s)
    *,
    n: int,  # real point count (sentinel id)
    np_cols: int,  # padded N+1 (chunk multiple)
    chunk: int,  # columns per one-hot tile
    d_real: int,
    n_entries: int,
    k: int,
    width: int,
    n_iters: int,
    expand: int,
    metric: str,
    stage: str,  # "f32" | "bf16" | "u8"
    rerank_k: int | None,
):
    r = graph_ref.shape[1]
    n_new = expand * r
    sentinel = jnp.int32(n)
    iota_w = jax.lax.broadcasted_iota(jnp.int32, (1, width), 1)
    n_chunks = np_cols // chunk

    def over_chunks(fn, init):
        """``fn(lanes, ids, carry)`` over the shard's column chunks: work
        that touches every column (one-hot gathers, visited tags) runs on
        ``[M, chunk]`` tiles, so VMEM and code size do not grow with N."""
        def step(c, carry):
            off = pl.multiple_of(c * chunk, chunk)
            ids = off + jax.lax.broadcasted_iota(jnp.int32, (1, chunk), 1)
            return fn(pl.ds(off, chunk), ids, carry)
        return jax.lax.fori_loop(0, n_chunks, step, init)

    # ---- prologue: the query's dense score row over the shard, staged in
    # sc_ref (f32 scores, or the int32 L2 code scores split lo/hi 16/16 so
    # the one-hot gathers stay integer-exact) ----
    if stage == "u8":
        s = s_ref[0, 0]
        zp = zp_ref[0, 0]
        qi = q_ref[...].astype(jnp.int32)
        cqn = jnp.sum(qi * qi)  # scalar query-code norm
        cqs = jnp.sum(qi)  # scalar query-code sum

        def prologue(lanes, _ids, carry):
            dots, _, _ = _u8_code_dots(q_ref[...], x_ref[lanes, :])
            if metric == "ip":  # absolute ip scores
                sc_ref[0:1, lanes] = -(
                    s * s * dots.astype(jnp.float32)
                    + s * zp * (cqs + xaux2_ref[:, lanes]).astype(
                        jnp.float32)
                    + d_real * zp * zp)
            else:
                sci = xaux1_ref[:, lanes] + cqn - 2 * dots  # [1, chunk]
                sc_ref[0:1, lanes] = (sci & 0xFFFF).astype(jnp.float32)
                sc_ref[1:2, lanes] = (sci >> 16).astype(jnp.float32)
            return carry
    else:
        s = zp = None

        def prologue(lanes, _ids, carry):
            w = w_ref[:, lanes]  # query·x from the batch matmul
            if metric == "ip":
                sc_ref[0:1, lanes] = -w
            else:
                sc_ref[0:1, lanes] = xaux1_ref[:, lanes] - 2.0 * w
            return carry
    over_chunks(prologue, 0)
    split = stage == "u8" and metric != "ip"

    def gather_part(lanes, eq, acc):
        """Add one chunk's one-hot gather of the score row to ``acc``
        (exact: a row's one non-zero sits in exactly one chunk)."""
        if not split:
            return acc + _gather_exact(sc_ref[0:1, lanes], eq, 3)
        lo = _gather_exact(sc_ref[0:1, lanes], eq, 2)
        hi = _gather_exact(sc_ref[1:2, lanes], eq, 2)
        return acc + jnp.concatenate([lo, hi], axis=0)

    def finish_scores(acc):
        if not split:
            return acc  # [1, M]
        sci = acc[1:2].astype(jnp.int32) * 65536 + acc[0:1].astype(jnp.int32)
        return jnp.maximum(sci.astype(jnp.float32), 0.0) * (s * s)

    # ---- seeding from the scalar-prefetch entries ----
    ci0 = jnp.full((1, width), sentinel, jnp.int32)
    ce0 = jnp.ones((1, width), jnp.int32)  # padding marked expanded
    for j in range(n_entries):
        e = ent_ref[j]
        ci0 = jnp.where(iota_w == j, e, ci0)
        ce0 = jnp.where(iota_w == j, 0, ce0)
    seed_col = jax.lax.broadcasted_iota(jnp.int32, (width, 1), 0)
    # one-hot per candidate slot against its id; padding slots gather the
    # spill column and are masked to inf below
    id_col = jnp.where(seed_col < n_entries, jnp.transpose(ci0), sentinel)
    n_acc = 2 if split else 1

    def seed(lanes, ids, acc):
        hit = id_col == ids  # [width, chunk]
        tags_ref[:, lanes] = (jnp.sum(
            jnp.where(hit & (seed_col < n_entries), 1, 0),
            axis=0, keepdims=True) > 0).astype(jnp.int32)  # seeds = 1
        return gather_part(lanes, hit.astype(jnp.bfloat16), acc)
    seed_d = finish_scores(
        over_chunks(seed, jnp.zeros((n_acc, width), jnp.float32)))
    cd_ref[...] = jnp.where(iota_w < n_entries, seed_d, jnp.inf)
    ci_ref[...] = ci0
    ce_ref[...] = ce0

    iota_nn_r = jax.lax.broadcasted_iota(jnp.int32, (1, n_new), 1)
    iota_nn_c = jax.lax.broadcasted_iota(jnp.int32, (n_new, 1), 0)

    def cond(carry):
        _nd, hops, _it, done = carry
        return jnp.logical_and(jnp.logical_not(done), hops < n_iters)

    def body(carry):
        n_dist, hops, it, done = carry
        cd = cd_ref[...]
        ci = ci_ref[...]
        ce = ce_ref[...]
        masked = jnp.where(ce != 0, jnp.inf, cd)
        # wavefront selection: `expand` sequential argmins, first-position
        # tie rule — exactly lax.top_k's (value, position) order
        selmask = jnp.zeros((1, width), bool)
        vs = []
        lives = []
        for _t in range(expand):
            m = jnp.min(masked)
            pos = jnp.min(jnp.where(masked == m, iota_w, width))
            lives.append(jnp.isfinite(m))
            vs.append(jnp.sum(jnp.where(iota_w == pos, ci, 0)))
            selmask = selmask | (iota_w == pos)
            masked = jnp.where(iota_w == pos, jnp.inf, masked)
        converged = jnp.logical_not(lives[0])
        halt = done | converged | (hops >= n_iters)
        # gather the wavefront's graph rows (scalar dynamic row slices —
        # the ids were just computed, so these are the VMEM-resident
        # equivalent of the prefetch-stream for larger-than-VMEM graphs)
        rows = [graph_ref[pl.ds(jnp.clip(v, 0, n - 1), 1), :] for v in vs]
        nbrs = jnp.concatenate(rows, axis=1)  # [1, n_new]
        # lane j of the wavefront belongs to selection j // r; masks are
        # built from int32 (Mosaic cannot concatenate or broadcast masks)
        blk = iota_nn_r // r
        live_i = jnp.zeros((1, n_new), jnp.int32)
        for t, lv in enumerate(lives):
            live_i = jnp.where(blk == t, lv.astype(jnp.int32), live_i)
        live_i = live_i * (1 - halt.astype(jnp.int32))
        valid = (nbrs >= 0) & (live_i != 0)
        safe_r = jnp.where(valid, nbrs, sentinel)  # [1, n_new]
        safe_c = jnp.transpose(safe_r)  # [n_new, 1]

        def seen_part(lanes, ids, acc):
            eq = (safe_c == ids).astype(jnp.bfloat16)  # [n_new, chunk]
            visited = (tags_ref[:, lanes] != 0).astype(jnp.bfloat16)
            return acc + jax.lax.dot_general(
                visited, eq, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)  # 0/1 sums: exact
        seen_r = over_chunks(
            seen_part, jnp.zeros((1, n_new), jnp.float32)) != 0.0
        cand = valid & ~seen_r
        # within-wavefront duplicate ids resolve to the *last* occurrence
        # (the jax backend's scatter semantics): drop j if a later k
        # carries the same id
        eqp = (safe_c == safe_r)  # [n_new(k↓? j↓), n_new]
        later = iota_nn_c < iota_nn_r  # element (j, k): k > j
        dup_later = jnp.sum(
            (eqp & later & cand).astype(jnp.int32), axis=1, keepdims=True
        ) > 0  # [n_new, 1] — j has a later duplicate candidate
        # transposes move int32, never masks (Mosaic transposes 32-bit)
        fresh_c = (jnp.transpose(cand.astype(jnp.int32)) != 0) & ~dup_later
        fresh_r = jnp.transpose(fresh_c.astype(jnp.int32)) != 0  # [1, n_new]
        slot_c = 2 + it * n_new + iota_nn_c  # [n_new, 1] int32

        def update_part(lanes, ids, acc):
            """Visited-tag update (winners write their unique slot) and
            the fresh neighbors' score gather, one chunk at a time."""
            hit = safe_c == ids  # [n_new, chunk]
            maxslot = jnp.max(jnp.where(hit & fresh_c, slot_c, 0), axis=0,
                              keepdims=True)  # [1, chunk]
            tags_ref[:, lanes] = jnp.where(maxslot > 0, maxslot,
                                           tags_ref[:, lanes])
            return gather_part(lanes, hit.astype(jnp.bfloat16), acc)
        nd = finish_scores(over_chunks(
            update_part, jnp.zeros((n_acc, n_new), jnp.float32)))
        nd_row = jnp.where(fresh_r, nd, jnp.inf)
        # bounded beam: keep the best `width` of (candidates ∪ fresh) by
        # (distance, position) — the bitonic network IS lax.top_k here
        total = width + n_new
        p2 = _next_pow2(total)
        all_d = _pad_lanes(jnp.concatenate([cd, nd_row], axis=1), p2,
                           jnp.inf)
        all_pos = jax.lax.broadcasted_iota(jnp.int32, (1, p2), 1)
        all_ids = _pad_lanes(
            jnp.concatenate([ci, jnp.where(fresh_r, nbrs, sentinel)],
                            axis=1), p2, sentinel)
        ce_u = jnp.where(selmask, 1, ce)
        all_exp = _pad_lanes(ce_u, p2, 0)
        sd, spos, (sids, sexp) = bitonic_sort_lex(
            all_d, all_pos, (all_ids, all_exp), tie_by_index=True
        )
        del spos
        keep_d = jnp.where(jnp.isfinite(sd[:, :width]), sd[:, :width],
                           jnp.inf)
        keep_ids = jnp.where(jnp.isfinite(sd[:, :width]),
                             sids[:, :width], sentinel)
        cd_ref[...] = jnp.where(halt, cd, keep_d)
        ci_ref[...] = jnp.where(halt, ci, keep_ids)
        ce_ref[...] = jnp.where(halt, ce, sexp[:, :width])
        n_fresh = jnp.sum(fresh_r.astype(jnp.int32))
        n_live = sum(lv.astype(jnp.int32) for lv in lives)
        n_dist = n_dist + jnp.where(halt, 0, n_fresh)
        hops = hops + jnp.where(halt, 0, n_live)
        return n_dist, hops, it + 1, done | converged

    n_dist, hops, _, _ = jax.lax.while_loop(
        cond, body,
        (jnp.int32(n_entries), jnp.int32(0), jnp.int32(0), jnp.bool_(False)),
    )

    # ---- final top-k: full (distance, position) sort of the list ----
    wp2 = _next_pow2(width)
    fin_d = _pad_lanes(cd_ref[...], wp2, jnp.inf)
    fin_ids = _pad_lanes(ci_ref[...], wp2, sentinel)
    fin_pos = jax.lax.broadcasted_iota(jnp.int32, (1, wp2), 1)
    sd, _, (sids,) = bitonic_sort_lex(
        fin_d, fin_pos, (fin_ids,), tie_by_index=True
    )
    top_d = sd[:, :k]
    top_ids = sids[:, :k]
    ok = jnp.isfinite(top_d) & (top_ids != sentinel)
    out_ids = jnp.where(ok, top_ids, -1)
    out_d = top_d
    if metric != "ip" and stage != "u8":
        qv = q_ref[...].astype(jnp.float32)
        out_d = out_d + jnp.sum(qv * qv)
    out_nd_ref[...] = jnp.full((1, 1), n_dist, jnp.int32)
    out_hops_ref[...] = jnp.full((1, 1), hops, jnp.int32)

    if rerank_k is None:
        out_ids_ref[...] = out_ids
        out_d_ref[...] = out_d
        out_nrr_ref[...] = jnp.zeros((1, 1), jnp.int32)
        return

    # ---- fused exact-f32 re-rank epilogue (VMEM-resident rows) ----
    qx = qex_ref[...]  # [1, Dx] f32
    iota_k = jax.lax.broadcasted_iota(jnp.int32, (1, k), 1)
    dex = jnp.zeros((1, k), jnp.float32)
    for j in range(k):
        cid = jnp.sum(jnp.where(iota_k == j, out_ids, 0))
        row = xex_ref[pl.ds(jnp.clip(cid, 0, n - 1), 1), :]  # [1, Dx]
        if metric == "ip":
            dj = -jnp.sum(row * qx)
        else:
            diff = row - qx
            dj = jnp.sum(diff * diff)
        dex = jnp.where(iota_k == j, dj, dex)
    valid = out_ids >= 0
    kp2 = _next_pow2(max(k, 2))
    d_key = _pad_lanes(jnp.where(valid, dex, jnp.inf), kp2, jnp.inf)
    id_key = _pad_lanes(jnp.where(valid, out_ids, _I32_MAX), kp2, _I32_MAX)
    sdex, sidex, _ = bitonic_sort_lex(d_key, id_key, tie_by_index=True)
    r_ids = sidex[:, :rerank_k]
    out_ids_ref[...] = jnp.where(r_ids == _I32_MAX, -1, r_ids)
    out_d_ref[...] = sdex[:, :rerank_k]
    out_nrr_ref[...] = jnp.full((1, 1), jnp.sum(valid.astype(jnp.int32)),
                                jnp.int32)


@functools.partial(
    jax.jit,
    static_argnames=("k", "width", "n_iters", "expand", "metric",
                     "rerank_k", "interpret"),
)
def _fused_beam_pallas(
    x: jax.Array,
    graph: jax.Array,
    entries: jax.Array,
    queries: jax.Array,
    scale: jax.Array,
    zp: jax.Array,
    x_exact,
    q_exact,
    *,
    k: int,
    width: int,
    n_iters: int,
    expand: int,
    metric: str,
    rerank_k: int | None,
    interpret: bool,
):
    """Pad, prepare the resident per-index constants, and launch one
    ``pallas_call`` over the query grid (same contract as
    :func:`_fused_beam_xla`)."""
    n, d = x.shape
    nq = queries.shape[0]
    is_u8 = x.dtype == jnp.uint8
    stage = "u8" if is_u8 else (
        "bf16" if x.dtype == jnp.bfloat16 else "f32")
    chunk, np_cols = _columns(n)
    d_pad = _round_up(d, LANE)
    # resident panels, padded to the lane grid (zero rows/columns are
    # exact for both metrics and both stages; see _u8_code_dots).  Operands
    # a stage does not read are one-tile dummies that keep one signature.
    gp = jnp.pad(graph, ((0, np_cols - n), (0, 0)), constant_values=-1)
    qp = jnp.pad(queries, ((0, 0), (0, d_pad - d)))
    if is_u8:
        xp = jnp.pad(x, ((0, np_cols - n), (0, d_pad - d)))
        w = jnp.zeros((nq, 1, LANE), jnp.float32)
        xi = x.astype(jnp.int32)
        aux1 = jnp.pad(
            jnp.sum(xi * xi, axis=1)[None, :], ((0, 0), (0, np_cols - n))
        )  # [1, Np] code norms
        aux2 = jnp.pad(
            jnp.sum(xi, axis=1)[None, :], ((0, 0), (0, np_cols - n))
        )  # [1, Np] code sums
    else:
        xp = jnp.zeros((8, LANE), x.dtype)
        xf = x.astype(jnp.float32)
        # the query·shard dots for the whole batch in one XLA matmul, the
        # same op as the XLA lowering's precomputed tile (so both lowerings
        # traverse on identical scores); each program reads its row
        w = jnp.pad(
            jnp.matmul(queries.astype(jnp.float32), xf.T, precision=_EXACT),
            ((0, 0), (0, np_cols - n)))[:, None, :]
        # zero pad (NOT inf): scores are gathered by one-hot *matmul*, and
        # 0·inf = NaN would poison every gathered lane.  Padded slots are
        # only reachable through masked sentinel gathers, so a finite pad
        # value is never observed.
        aux1 = jnp.pad(
            jnp.sum(xf * xf, axis=1)[None, :], ((0, 0), (0, np_cols - n))
        )  # [1, Np] norms
        aux2 = jnp.zeros((1, np_cols), jnp.int32)
    if rerank_k is not None:
        dx = x_exact.shape[1]
        dx_pad = _round_up(dx, LANE)
        xex = jnp.pad(x_exact, ((0, np_cols - n), (0, dx_pad - dx)))
        qex = jnp.pad(q_exact, ((0, 0), (0, dx_pad - dx)))
    else:
        dx_pad = LANE
        xex = jnp.zeros((8, dx_pad), jnp.float32)
        qex = jnp.zeros((nq, dx_pad), jnp.float32)
    k_out = rerank_k if rerank_k is not None else k

    kernel = functools.partial(
        _beam_kernel,
        n=n, np_cols=np_cols, chunk=chunk, d_real=d,
        n_entries=entries.shape[0],
        k=k, width=width, n_iters=n_iters, expand=expand, metric=metric,
        stage=stage, rerank_k=rerank_k,
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(nq,),
        in_specs=[
            pl.BlockSpec((None, 1, d_pad), lambda i, ent: (i, 0, 0)),
            pl.BlockSpec((None, 1, w.shape[2]), lambda i, ent: (i, 0, 0)),
            pl.BlockSpec(xp.shape, lambda i, ent: (0, 0)),
            pl.BlockSpec((np_cols, graph.shape[1]), lambda i, ent: (0, 0)),
            pl.BlockSpec((1, np_cols), lambda i, ent: (0, 0)),
            pl.BlockSpec((1, np_cols), lambda i, ent: (0, 0)),
            pl.BlockSpec((1, 1), lambda i, ent: (0, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1), lambda i, ent: (0, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec(xex.shape, lambda i, ent: (0, 0)),
            pl.BlockSpec((None, 1, dx_pad), lambda i, ent: (i, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, 1, k_out), lambda i, ent: (i, 0, 0)),
            pl.BlockSpec((None, 1, k_out), lambda i, ent: (i, 0, 0)),
            pl.BlockSpec((None, 1, 1), lambda i, ent: (i, 0, 0)),
            pl.BlockSpec((None, 1, 1), lambda i, ent: (i, 0, 0)),
            pl.BlockSpec((None, 1, 1), lambda i, ent: (i, 0, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((1, np_cols), jnp.int32),  # visited tags
            pltpu.VMEM((1, width), jnp.float32),  # candidate distances
            pltpu.VMEM((1, width), jnp.int32),  # candidate ids
            pltpu.VMEM((1, width), jnp.int32),  # expanded flags
            pltpu.VMEM((2, np_cols), jnp.float32),  # score row(s)
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((nq, 1, k_out), jnp.int32),
            jax.ShapeDtypeStruct((nq, 1, k_out), jnp.float32),
            jax.ShapeDtypeStruct((nq, 1, 1), jnp.int32),
            jax.ShapeDtypeStruct((nq, 1, 1), jnp.int32),
            jax.ShapeDtypeStruct((nq, 1, 1), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
    )(entries, qp[:, None, :], w, xp, gp, aux1, aux2,
      jnp.reshape(scale, (1, 1)), jnp.reshape(zp, (1, 1)), xex,
      qex[:, None, :])
    ids, ds, nd, hp, nrr = out
    return ids[:, 0], ds[:, 0], nd[:, 0, 0], hp[:, 0, 0], nrr[:, 0, 0]


# ---------------------------------------------------------------------------
# Dispatch
# ---------------------------------------------------------------------------


def default_lowering() -> str:
    """Pick the lowering from the repo-wide Pallas dispatch policy
    (:func:`repro.kernels.ops.pallas_mode`): the kernel on TPU (or under
    ``force_interpret`` for CI validation), the flat-batch XLA path
    elsewhere — which is the serving-speed path on CPU hosts."""
    from repro.kernels import ops  # deferred: ops imports this module's
    # siblings; keep module import light

    use, interp = ops._use_pallas()
    if use:
        return "pallas_interpret" if interp else "pallas"
    return "xla"


def fused_beam(
    x: jax.Array,  # [N, D] f32 / bf16 / uint8 codes (device or host)
    graph: jax.Array,  # [N, R] int32
    entries: jax.Array,  # [E] int32, E <= width
    queries: jax.Array,  # [Q, D] matching the stage (codes for uint8)
    k: int,
    *,
    width: int = 64,
    n_iters: int | None = None,
    expand: int = DEFAULT_EXPAND,
    metric: str = "l2",
    scale=0.0,
    zp=0.0,
    x_exact: jax.Array | None = None,  # [N, Dx] f32 — fused re-rank rows
    q_exact: jax.Array | None = None,  # [Q, Dx] f32
    rerank_k: int | None = None,
    lowering: str | None = None,
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array, jax.Array]:
    """The fused traversal(+re-rank) op: one dispatch per batch.

    Returns ``(ids [Q, k_out] int32 with -1 padding, dists [Q, k_out]
    f32, n_dist [Q] int32, hops [Q] int32, n_rerank [Q] int32)`` with
    ``k_out = rerank_k or k``; ``n_rerank`` is 0 without the epilogue.

    ``lowering`` — ``None`` (policy dispatch via :func:`default_lowering`),
    ``"xla"``, ``"pallas"``, or ``"pallas_interpret"`` (tests pin lowerings
    explicitly for the bit-parity matrix).
    """
    if n_iters is None:
        n_iters = width + width // 2  # jax_backend.default_n_iters
    if rerank_k is not None and (x_exact is None or q_exact is None):
        raise ValueError("rerank_k requires x_exact and q_exact")
    lowering = lowering or default_lowering()
    x = jnp.asarray(x)
    graph = jnp.asarray(graph, jnp.int32)
    entries = jnp.asarray(entries, jnp.int32)
    queries = jnp.asarray(queries)
    scale = jnp.float32(scale)
    zp = jnp.float32(zp)
    if x_exact is not None:
        x_exact = jnp.asarray(x_exact, jnp.float32)
        q_exact = jnp.asarray(q_exact, jnp.float32)
    if lowering == "xla":
        n = x.shape[0]
        precompute = (
            x.dtype != jnp.uint8
            and queries.shape[0] * (n + 1) <= PRECOMPUTE_TILE_LIMIT
        )
        return _fused_beam_xla(
            x, graph, entries, queries, scale, zp, x_exact, q_exact,
            k=k, width=width, n_iters=n_iters, expand=expand,
            metric=metric, rerank_k=rerank_k, precompute=precompute,
        )
    if lowering not in ("pallas", "pallas_interpret"):
        raise ValueError(f"unknown fused_beam lowering {lowering!r}")
    need = pallas_vmem_bytes(
        x.shape[0], x.shape[1], graph.shape[1], x.dtype,
        dx=None if x_exact is None else x_exact.shape[1])
    if need > VMEM_LIMIT_BYTES:
        raise ValueError(
            f"fused_beam Pallas lowering: a [{x.shape[0]}, {x.shape[1]}] "
            f"{x.dtype} shard of degree {graph.shape[1]} needs {need} bytes "
            f"of resident VMEM, above the {VMEM_LIMIT_BYTES}-byte bound it "
            "compiles under")
    return _fused_beam_pallas(
        x, graph, entries, queries, scale, zp, x_exact, q_exact,
        k=k, width=width, n_iters=n_iters, expand=expand, metric=metric,
        rerank_k=rerank_k, interpret=lowering == "pallas_interpret",
    )
