"""Two-level guided tile-scan traversal — the paper's algorithm, TPU-native.

The docid space is scanned tile-by-tile in docid order (``lax.scan``),
carrying three top-k queues whose thresholds tighten monotonically — the
DAAT threshold dynamic at tile granularity. Per tile:

  1. *Tile skip* (global level): sum of alpha-combined per-(term,tile) maxima
     <= theta_Gl  =>  no doc in the tile can qualify; skip.
  2. *Term partitioning* (global level): terms presorted ascending by
     alpha-combined list maxima; the prefix whose bound sum stays <= theta_Gl
     is non-essential. Docs with no essential-term posting are pruned and
     enter no queue.
  3. *Local level*: surviving docs accumulate weights term-by-term in
     descending order. Before each non-essential term, docs whose
     beta-partial + beta-combined remaining bound <= theta_Lo freeze: they
     stop accumulating but keep their partial gamma-combined RankScore,
     which still enters Q_Rk (paper queue discipline).
  4. Tile-local top-k of Global/Local/Rank merge into the carried queues.

Planner/executor split (see ``core.plan`` for the full contract): term
sorting, tile scheduling, bound computation and the theta_Gl partition all
live in the planner; this module holds the *executors* — ``score_tile``
(pure jnp) and ``_score_tile_kernel`` (fused Pallas ``guided_score``) share
one contract ``(offs, wb, wl, essential, prefix_beta, th_lo, ...)`` and are
interchangeable per ``use_kernel``. ``_tile_step`` is the executor step
driven by every traversal mode:

  - ``retrieve_batched`` (``traversal="full"``): vmap over queries x
    lax.scan over tiles (TPU path; skipped tiles are masked compute).
  - ``retrieve_batched`` (``traversal="chunked"``/``"chunked_fused"``):
    descending-bound tile chunks under a ``lax.while_loop`` that stops at
    the first bound-failing chunk — *real* work elision under jit
    (Block-Max-Pruning structure; see ``_retrieve_chunked_impl``).
  - ``retrieve_sequential``: host loop with *physical* tile skipping, timing
    each query — the paper's single-threaded latency regime.
  - ``serve.sharded.shard_retrieve_batched``: per-shard tile scans under
    ``shard_map`` with a collective top-k merge (same step, same planner).
"""
from __future__ import annotations

import dataclasses
import time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from ..obs.spans import scope
from .index import BlockedImpactIndex, dispatch_gather, gather_tile
from .plan import (QueryPlan, chunk_schedule, combine, essential_terms,
                   freeze_bounds, plan_query, term_bounds, tile_schedule,
                   tile_upper_bounds)
from .twolevel import TwoLevelParams, resolve_k

NEG_INF = jnp.float32(-jnp.inf)

# Kept under the historical name: kernel tests exercise the executor's
# combination directly.
_combine = combine

STAT_KEYS = ("docs_present", "docs_survived", "docs_frozen",
             "postings_touched", "tiles_visited")

# The per-query counters worth attaching to a request's execute span:
# the executor stats plus the chunked traversal's dispatch counts
# (absent from engines that don't produce them). Consumed by
# ``repro.obs.trace_exec`` — keep in sync with retrieve_batched's stats
# assembly below.
TRACE_STAT_KEYS = STAT_KEYS + ("n_tiles", "chunks_dispatched", "n_chunks")


@dataclasses.dataclass
class RetrievalResult:
    ids: np.ndarray        # [B, k] int32 (Q_Rk docids, score-desc)
    scores: np.ndarray     # [B, k] float32 (RankScore)
    global_ids: np.ndarray
    local_ids: np.ndarray
    stats: dict            # per-query counters
    latencies_ms: np.ndarray | None = None  # sequential mode only


def _merge_queue(q_vals, q_ids, c_vals, c_ids, k: int):
    """Merge tile candidates into a sorted top-k queue (stable ties)."""
    vals = jnp.concatenate([q_vals, c_vals])
    ids = jnp.concatenate([q_ids, c_ids])
    top_vals, idx = jax.lax.top_k(vals, k)
    return top_vals, ids[idx]


def _tile_topk(scores, mask, kq: int):
    vals, idx = jax.lax.top_k(jnp.where(mask, scores, NEG_INF), kq)
    return vals, idx.astype(jnp.int32)


def score_tile(offs, wb, wl, essential, prefix_beta, th_lo,
               alpha, beta, gamma, *, tile_size: int, kq: int):
    """Score one tile for one query. See module docstring for the levels.

    offs:        [Nq, P] int32 local doc offsets (-1 = padding)
    wb, wl:      [Nq, P] f32 query-weighted posting weights (0 = padding)
    essential:   [Nq] bool essential-term mask (planner, sorted order)
    prefix_beta: [Nq] f32 inclusive beta-bound prefix sums (planner)
    Returns three (vals, local_idx) candidate sets + stat counters.
    """
    nq = offs.shape[0]
    S = tile_size
    valid = offs >= 0
    offs_safe = jnp.where(valid, offs, S).astype(jnp.int32)

    # Dense per-term rows: one scatter for all terms at once.
    seg = (jnp.arange(nq, dtype=jnp.int32)[:, None] * (S + 1) + offs_safe).ravel()
    dense_b = jax.ops.segment_sum(wb.ravel(), seg, num_segments=nq * (S + 1)
                                  ).reshape(nq, S + 1)[:, :S]
    dense_l = jax.ops.segment_sum(wl.ravel(), seg, num_segments=nq * (S + 1)
                                  ).reshape(nq, S + 1)[:, :S]
    cnt = jax.ops.segment_sum(valid.ravel().astype(jnp.float32), seg,
                              num_segments=nq * (S + 1)).reshape(nq, S + 1)[:, :S]

    present = cnt.sum(0) > 0                               # [S]
    ess_cnt = jnp.einsum("t,ts->s", essential.astype(jnp.float32), cnt)
    survive = ess_cnt > 0                                  # [S]

    # Local level: descending accumulate with freeze checks.
    def body(j, state):
        i = nq - 1 - j
        sb, sl, alive = state
        l_part = combine(beta, sb, sl)
        ok = essential[i] | (l_part + prefix_beta[i] > th_lo)
        alive = alive & ok
        gate = (survive & alive).astype(sb.dtype)
        sb = sb + gate * dense_b[i]
        sl = sl + gate * dense_l[i]
        return sb, sl, alive

    sb0 = jnp.zeros(S, dtype=jnp.float32)
    alive0 = jnp.ones(S, dtype=bool)
    sb, sl, alive = jax.lax.fori_loop(0, nq, body, (sb0, sb0, alive0))

    g = combine(alpha, sb, sl)
    l = combine(beta, sb, sl)
    r = combine(gamma, sb, sl)
    eval_mask = survive & alive
    rank_mask = survive

    g_c = _tile_topk(g, eval_mask, kq)
    l_c = _tile_topk(l, eval_mask, kq)
    r_c = _tile_topk(r, rank_mask, kq)
    stats = jnp.stack([present.sum().astype(jnp.float32),
                       survive.sum().astype(jnp.float32),
                       (survive & ~alive).sum().astype(jnp.float32),
                       valid.sum().astype(jnp.float32)])
    return g_c, l_c, r_c, stats


def _kernel_stats(out):
    """Per-tile counters from a chunk of kernel outputs [C, 6, S]:
    presence and postings touched from the per-slot posting count (row 5),
    survivors and frozen docs from the masks. Offsets within a run are
    distinct and padding matches no slot, so these equal ``score_tile``'s
    scatter counts exactly. Returns [C, 4]."""
    eval_mask = out[:, 3] > 0
    rank_mask = out[:, 4] > 0
    slot_cnt = out[:, 5]
    return jnp.stack(
        [(slot_cnt > 0).sum(1).astype(jnp.float32), out[:, 4].sum(1),
         (rank_mask & ~eval_mask).sum(1).astype(jnp.float32),
         slot_cnt.sum(1)], axis=1)


def _score_tile_kernel(offs, wb, wl, essential, prefix_beta, th_lo,
                       alpha, beta, gamma, *, tile_size: int, kq: int):
    """Pallas guided_score kernel path (interpret mode on CPU): same
    contract as ``score_tile``; the fused kernel returns G/L/R, the masks
    and the per-slot posting count the stats read."""
    from ..kernels.guided_score import guided_score_tile
    out = guided_score_tile(offs, wb, wl, essential.astype(jnp.float32),
                            prefix_beta, th_lo, alpha, beta, gamma,
                            tile_size=tile_size,
                            block_s=min(512, tile_size))
    g, l, r, eval_m, rank_m, _ = out
    eval_mask = eval_m > 0
    rank_mask = rank_m > 0
    with jax.named_scope("stats"):
        stats = _kernel_stats(out[None])[0]
    return (_tile_topk(g, eval_mask, kq), _tile_topk(l, eval_mask, kq),
            _tile_topk(r, rank_mask, kq), stats)


def _gather_tile(docids, w_b, w_l, tile_ptr, qt, qwb, qwl, tile,
                 *, pad_len: int, tile_size: int):
    """Query-weighted padded tile gather — delegates to the single gather
    implementation in ``core.index.gather_tile``."""
    return gather_tile(docids, w_b, w_l, tile_ptr, qt, tile, qwb, qwl,
                       pad_len=pad_len, tile_size=tile_size)


def _score_tile_kernel_q(gt, plan: QueryPlan, tile, essential, prefix_beta,
                         th_lo, alpha, beta, gamma,
                         *, tile_size: int, pad_len: int, kq: int):
    """Decode-in-kernel Pallas path for the compressed index: raw packed
    rows go straight into ``guided_score_tile_q``, which delta-decodes the
    offsets and dequantizes the impacts in VMEM before the shared scatter/
    freeze passes. Same candidate contract as ``score_tile``; stats come
    from the kernel's per-slot posting-count row (no second decode)."""
    from ..index.compressed import gather_tile_q_raw
    from ..kernels.guided_score import guided_score_tile_q
    with jax.named_scope("gather"):
        words, qb_row, ql_row, meta_i, meta_f = gather_tile_q_raw(
            gt, plan.qt, tile, plan.qwb, plan.qwl, pad_len=pad_len)
    with jax.named_scope("score"):
        out = guided_score_tile_q(
            words, qb_row, ql_row, meta_i, meta_f, plan.qwb, plan.qwl,
            essential.astype(jnp.float32), prefix_beta, th_lo,
            alpha, beta, gamma, tile_size=tile_size, pad_len=pad_len,
            block_s=min(512, tile_size))
        g, l, r, eval_m, rank_m, _ = out
        eval_mask = eval_m > 0
        rank_mask = rank_m > 0
        candidates = (_tile_topk(g, eval_mask, kq),
                      _tile_topk(l, eval_mask, kq),
                      _tile_topk(r, rank_mask, kq))
    with jax.named_scope("stats"):
        stats = _kernel_stats(out[None])[0]
    return (*candidates, stats)


def _tile_step(idx_arrays, plan: QueryPlan, carry, tile,
               alpha, beta, gamma, factor,
               *, k, kq, pad_len, tile_size, bound_mode, use_kernel=False,
               gather_kind="fp32", th_floor=None, tile_valid=None):
    """One tile visit: plan bounds -> skip test -> score -> queue merge.

    ``idx_arrays`` is ``(gather_tuple, tile_max_b, tile_max_l)`` — the
    index's ``gather_arrays()`` payload plus the exact fp32 tile maxima;
    ``gather_kind`` (static) selects the decoder, so the same step serves
    the fp32 and compressed indexes. Planning reads only the exact maxima,
    which both index types carry — bounds and skip decisions are
    codec-independent by construction.

    ``th_floor`` (optional scalar) is an externally supplied lower bound on
    theta_Gl — the sharded path injects the exchanged global threshold here
    so a shard prunes against the global queue, not just its local one.
    Thresholds only tighten, so any floor <= the true global theta is safe.

    ``tile_valid`` (optional bool) force-skips the visit when False — the
    sharded path marks its shape-padding tiles invalid so they never enter
    queues or stats and skip rates stay comparable across engines.
    """
    gt, tile_max_b, tile_max_l = idx_arrays
    (gv, gi, lv, li, rv, ri, st) = carry
    th_gl = gv[-1]
    if th_floor is not None:
        th_gl = jnp.maximum(th_gl, th_floor)
    th_gl = th_gl * factor
    th_lo = lv[-1] * factor

    with jax.named_scope("bounds"):
        m_alpha, m_beta, ub_gl = term_bounds(plan, tile_max_b, tile_max_l,
                                             tile, alpha, beta, bound_mode)
        skip = ub_gl <= th_gl
        if tile_valid is not None:
            skip = skip | ~tile_valid
        essential = essential_terms(m_alpha, th_gl)
        prefix_beta = freeze_bounds(m_beta)

    if use_kernel and gather_kind == "q8":
        # compressed + kernel: decode happens inside the pallas_call
        g_c, l_c, r_c, stats = _score_tile_kernel_q(
            gt, plan, tile, essential, prefix_beta, th_lo,
            alpha, beta, gamma, tile_size=tile_size, pad_len=pad_len, kq=kq)
    else:
        with jax.named_scope("gather"):
            offs, wb, wl = dispatch_gather(gather_kind, gt, plan.qt, tile,
                                           plan.qwb, plan.qwl,
                                           pad_len=pad_len,
                                           tile_size=tile_size)
        scorer = _score_tile_kernel if use_kernel else score_tile
        with jax.named_scope("score"):
            g_c, l_c, r_c, stats = scorer(
                offs, wb, wl, essential, prefix_beta, th_lo, alpha, beta,
                gamma, tile_size=tile_size, kq=kq)

    base = tile * tile_size

    def masked(c):
        vals, idx = c
        vals = jnp.where(skip, NEG_INF, vals)
        return vals, base + idx

    with jax.named_scope("merge"):
        gv, gi = _merge_queue(gv, gi, *masked(g_c), k)
        lv, li = _merge_queue(lv, li, *masked(l_c), k)
        rv, ri = _merge_queue(rv, ri, *masked(r_c), k)
        visited = jnp.where(skip, 0.0, 1.0)
        st = st + jnp.concatenate([jnp.where(skip, 0.0, stats),
                                   visited[None]])
    return (gv, gi, lv, li, rv, ri, st)


def _init_carry(k):
    vals = jnp.full(k, NEG_INF, dtype=jnp.float32)
    ids = jnp.full(k, -1, dtype=jnp.int32)
    return (vals, ids, vals, ids, vals, ids, jnp.zeros(5, dtype=jnp.float32))


TRAVERSALS = ("full", "chunked", "chunked_fused")


def _chunk_scan(idx_arrays, plan, carry, tiles_chunk, alpha, beta, gamma,
                factor, n_valid, *, th_floor=None, **statics):
    """Advance one query's carry over one chunk of its tile order.

    Exact per-tile semantics: every tile re-reads the carry's thresholds,
    so the operation sequence is identical to the full scan's — the chunk
    grouping only decides how much of the schedule is dispatched at all.
    ``n_valid`` force-skips sentinel/padding tiles (id >= n_valid)."""
    def step(c, tile):
        return _tile_step(idx_arrays, plan, c, tile, alpha, beta, gamma,
                          factor, th_floor=th_floor,
                          tile_valid=tile < n_valid, **statics), None
    return jax.lax.scan(step, carry, tiles_chunk)[0]


def _chunk_while(advance, chunk_ub, carries, disp, th_floor, factor):
    """Early-exit loop over a chunk sequence — the single copy of the
    Block-Max-Pruning termination rule, shared by the batched executor
    and the sharded per-shard rounds (``serve.sharded._chunk_round``).

    Dispatches chunk ``i`` (``advance(i, carries)``) while any query's
    next chunk bound beats its (floored) theta_Gl; per-chunk bounds are
    descending and thresholds only tighten, so the first failing chunk
    proves every later tile fails its per-tile skip test too. ``disp``
    accumulates the per-query count of chunks that were live when
    dispatched. All operands are batched over queries ([B] leading dim);
    ``th_floor`` is -inf when no exchanged global theta applies."""
    n_c = chunk_ub.shape[1]

    def th_of(carries):
        return jnp.maximum(carries[0][:, -1], th_floor) * factor

    def cond(state):
        i, carries, _ = state
        ub_i = jax.lax.dynamic_index_in_dim(chunk_ub, i, 1, False)
        return (i < n_c) & jnp.any(ub_i > th_of(carries))

    def body(state):
        i, carries, disp = state
        ub_i = jax.lax.dynamic_index_in_dim(chunk_ub, i, 1, False)
        active = ub_i > th_of(carries)
        carries = advance(i, carries)
        return i + 1, carries, disp + active.astype(jnp.float32)

    _, carries, disp = jax.lax.while_loop(
        cond, body, (jnp.int32(0), carries, disp))
    return carries, disp


def _chunk_step_fused(idx_arrays, plan, carry, tiles_chunk,
                      alpha, beta, gamma, factor, n_valid,
                      *, k, kq, pad_len, tile_size, bound_mode,
                      gather_kind="fp32", th_floor=None):
    """Advance one query's carry over one chunk via the multi-tile Pallas
    ``guided_score_chunk`` kernel (one pallas_call per chunk; the ``_q``
    decode-in-kernel variant when the index is compressed).

    The skip predicate, essential partition and freeze bounds for every
    tile in the chunk derive from the *chunk-start* thresholds (the carry
    cannot be updated mid-kernel). Within a chunk that only loosens the
    pruning, so rank-safe configs stay bound-exact; guided configs follow
    a slightly different (still bound-safe) threshold trajectory — the
    usual guided tolerance, pinned in test_traversal."""
    from ..kernels.guided_score import guided_score_chunk, guided_score_chunk_q
    gt, tile_max_b, tile_max_l = idx_arrays
    gv, gi, lv, li, rv, ri, st = carry
    th_gl = gv[-1]
    if th_floor is not None:
        th_gl = jnp.maximum(th_gl, th_floor)
    th_gl = th_gl * factor
    th_lo = lv[-1] * factor

    with jax.named_scope("bounds"):
        m_alpha, m_beta, ub_gl = jax.vmap(
            lambda t: term_bounds(plan, tile_max_b, tile_max_l, t,
                                  alpha, beta, bound_mode))(tiles_chunk)
        skip = (ub_gl <= th_gl) | (tiles_chunk >= n_valid)    # [C]
        essential = jax.vmap(essential_terms, in_axes=(0, None))(m_alpha,
                                                                 th_gl)
        prefix_beta = jax.vmap(freeze_bounds)(m_beta)

    if gather_kind == "q8":
        from ..index.compressed import gather_tile_q_raw
        with jax.named_scope("gather"):
            words, qbr, qlr, meta_i, meta_f = jax.vmap(
                lambda t: gather_tile_q_raw(gt, plan.qt, t, plan.qwb,
                                            plan.qwl, pad_len=pad_len)
            )(tiles_chunk)
        with jax.named_scope("score"):
            out = guided_score_chunk_q(
                words, qbr, qlr, meta_i, meta_f, plan.qwb, plan.qwl,
                essential.astype(jnp.float32), prefix_beta, skip, th_lo,
                alpha, beta, gamma, tile_size=tile_size, pad_len=pad_len,
                block_s=min(512, tile_size))
    else:
        docids, w_b, w_l, tile_ptr = gt
        with jax.named_scope("gather"):
            offs, wb, wl = jax.vmap(
                lambda t: _gather_tile(docids, w_b, w_l, tile_ptr,
                                       plan.qt, plan.qwb, plan.qwl, t,
                                       pad_len=pad_len, tile_size=tile_size)
            )(tiles_chunk)                                    # [C, Nq, P]
        with jax.named_scope("score"):
            out = guided_score_chunk(
                offs, wb, wl, essential.astype(jnp.float32), prefix_beta,
                skip, th_lo, alpha, beta, gamma, tile_size=tile_size,
                block_s=min(512, tile_size))

    g, l, r = out[:, 0], out[:, 1], out[:, 2]
    eval_mask = out[:, 3] > 0
    rank_mask = out[:, 4] > 0
    with jax.named_scope("stats"):
        tile_stats = _kernel_stats(out)                       # [C, 4]

    def merge_step(c, xs):
        gv, gi, lv, li, rv, ri, st = c
        tile, g_t, l_t, r_t, ev_t, rk_t, sk_t, st_t = xs
        base = tile * tile_size

        def masked(cand):
            vals, idx = cand
            return jnp.where(sk_t, NEG_INF, vals), base + idx
        gv, gi = _merge_queue(gv, gi, *masked(_tile_topk(g_t, ev_t, kq)), k)
        lv, li = _merge_queue(lv, li, *masked(_tile_topk(l_t, ev_t, kq)), k)
        rv, ri = _merge_queue(rv, ri, *masked(_tile_topk(r_t, rk_t, kq)), k)
        visited = jnp.where(sk_t, 0.0, 1.0)
        st = st + jnp.concatenate([jnp.where(sk_t, 0.0, st_t),
                                   visited[None]])
        return (gv, gi, lv, li, rv, ri, st), None
    with jax.named_scope("merge"):
        carry, _ = jax.lax.scan(
            merge_step, carry,
            (tiles_chunk, g, l, r, eval_mask, rank_mask, skip, tile_stats))
    return carry


@partial(jax.jit, static_argnames=("k", "kq", "pad_len", "tile_size",
                                   "n_tiles", "bound_mode", "chunk_tiles",
                                   "use_kernel", "fused", "gather_kind"))
def _retrieve_chunked_impl(gt, tile_max_b, tile_max_l,
                           sigma_b, sigma_l, q_terms, qw_b, qw_l,
                           alpha, beta, gamma, factor,
                           *, k, kq, pad_len, tile_size, n_tiles, bound_mode,
                           chunk_tiles, use_kernel=False, fused=False,
                           gather_kind="fp32"):
    """Chunked traversal: real skipping under jit.

    Tiles are presorted by descending global upper bound and folded into
    static ``[n_chunks, chunk_tiles]`` groups (``core.plan.chunk_schedule``);
    a ``lax.while_loop`` dispatches one chunk per iteration and terminates
    at the first chunk whose max bound fails the theta_Gl test. Bounds
    descend and thresholds only tighten, so every undispatched tile would
    have been skipped by the full impact-ordered scan anyway — results and
    stats are bit-identical to it while a fraction of the chunks execute.
    Under vmap-over-queries the loop runs until every query's bound fails
    (per-query ``chunks_dispatched`` still counts each query's own work).
    """
    idx_arrays = (gt, tile_max_b, tile_max_l)

    def plan_one(qt, qwb, qwl):
        plan = plan_query(qt, qwb, qwl, sigma_b, sigma_l, alpha)
        sched = chunk_schedule(plan, tile_max_b, tile_max_l, alpha,
                               n_tiles, chunk_tiles)
        return plan, sched
    plans, sched = jax.vmap(plan_one)(q_terms, qw_b, qw_l)
    chunks, chunk_ub = sched          # [B, n_chunks, C], [B, n_chunks]
    b = q_terms.shape[0]
    carries = jax.tree_util.tree_map(
        lambda x: jnp.broadcast_to(x, (b,) + x.shape), _init_carry(k))
    statics = dict(k=k, kq=kq, pad_len=pad_len, tile_size=tile_size,
                   bound_mode=bound_mode, gather_kind=gather_kind)

    if fused:
        def step_one(plan, tiles_i, carry):
            return _chunk_step_fused(idx_arrays, plan, carry, tiles_i,
                                     alpha, beta, gamma, factor, n_tiles,
                                     **statics)
    else:
        def step_one(plan, tiles_i, carry):
            return _chunk_scan(idx_arrays, plan, carry, tiles_i,
                               alpha, beta, gamma, factor, n_tiles,
                               use_kernel=use_kernel, **statics)

    def advance(i, carries):
        tiles_i = jax.lax.dynamic_index_in_dim(chunks, i, 1, False)
        return jax.vmap(step_one)(plans, tiles_i, carries)

    return _chunk_while(advance, chunk_ub, carries,
                        jnp.zeros(b, jnp.float32),
                        jnp.full(b, -jnp.inf, jnp.float32), factor)


@partial(jax.jit, static_argnames=("k", "kq", "pad_len", "tile_size",
                                   "n_tiles", "bound_mode", "schedule",
                                   "use_kernel", "gather_kind"))
def _retrieve_batched_impl(gt, tile_max_b, tile_max_l,
                           sigma_b, sigma_l, q_terms, qw_b, qw_l,
                           alpha, beta, gamma, factor,
                           *, k, kq, pad_len, tile_size, n_tiles, bound_mode,
                           schedule, use_kernel=False, gather_kind="fp32"):
    idx_arrays = (gt, tile_max_b, tile_max_l)

    def one_query(qt, qwb, qwl):
        plan = plan_query(qt, qwb, qwl, sigma_b, sigma_l, alpha)
        tiles = tile_schedule(plan, tile_max_b, tile_max_l, alpha,
                              n_tiles, schedule)

        def step(carry, tile):
            carry = _tile_step(idx_arrays, plan, carry, tile,
                               alpha, beta, gamma, factor,
                               k=k, kq=kq, pad_len=pad_len,
                               tile_size=tile_size, bound_mode=bound_mode,
                               use_kernel=use_kernel,
                               gather_kind=gather_kind)
            return carry, None

        carry, _ = jax.lax.scan(step, _init_carry(k), tiles)
        return carry

    return jax.vmap(one_query)(q_terms, qw_b, qw_l)


def retrieve_batched(index: BlockedImpactIndex, q_terms, qw_b, qw_l,
                     params: TwoLevelParams,
                     use_kernel: bool = False,
                     k: int | None = None,
                     traversal: str = "full",
                     chunk_tiles: int | None = None) -> RetrievalResult:
    """Batched retrieval: q_terms [B, Nq] int32 (pad with qw = 0).

    ``index`` may be a ``BlockedImpactIndex`` or a
    ``repro.index.CompressedImpactIndex`` — both expose the same planner
    metadata and a ``gather_arrays()``/``gather_kind`` pair; the executors
    decode compressed postings inside the gather (or inside the Pallas
    kernel when ``use_kernel=True``).

    ``k`` is the retrieval depth for this call (falls back to the
    deprecated ``params.k`` stash, then DEFAULT_K). ``use_kernel=True``
    routes tile scoring through the fused Pallas guided_score kernel
    (native on TPU; interpreter elsewhere).

    ``traversal``:
      - ``"full"`` — lax.scan over all tiles in ``params.schedule`` order;
        skipped tiles are masked compute (the historical engine).
      - ``"chunked"`` — descending-bound tile chunks under a
        ``lax.while_loop`` that stops at the first bound-failing chunk:
        bit-identical (ids, scores, stats) to the full scan with the
        ``impact`` schedule while dispatching only the live chunk prefix.
        Stats gain ``chunks_dispatched`` / ``n_chunks``.
      - ``"chunked_fused"`` — same chunk loop, but each chunk is scored by
        one multi-tile ``guided_score_chunk`` pallas_call whose skip/
        essential/freeze inputs come from the chunk-start thresholds:
        rank-safe configs stay exact; guided configs track the exact
        chunked path within the usual guided tolerance.
    ``chunk_tiles`` overrides ``params.chunk_tiles`` for this call.
    """
    if traversal not in TRAVERSALS:
        raise ValueError(f"traversal must be in {TRAVERSALS}, "
                         f"got {traversal!r}")
    k = resolve_k(params, k)
    kq = min(k, index.tile_size)
    with scope("dispatch"):        # host-to-device and enqueue
        q_terms = jnp.asarray(q_terms, dtype=jnp.int32)
        qw_b = jnp.asarray(qw_b, dtype=jnp.float32)
        qw_l = jnp.asarray(qw_l, dtype=jnp.float32)
        arrays = (index.gather_arrays(),
                  index.tile_max_b, index.tile_max_l,
                  index.sigma_b, index.sigma_l, q_terms, qw_b, qw_l,
                  jnp.float32(params.alpha), jnp.float32(params.beta),
                  jnp.float32(params.gamma),
                  jnp.float32(params.threshold_factor))
        statics = dict(k=k, kq=kq, pad_len=index.pad_len,
                       tile_size=index.tile_size,
                       bound_mode=params.bound_mode,
                       gather_kind=index.gather_kind)
        disp = None
        if traversal == "full":
            out = _retrieve_batched_impl(*arrays, n_tiles=index.n_tiles,
                                         schedule=params.schedule,
                                         use_kernel=use_kernel, **statics)
        else:
            ct = int(chunk_tiles if chunk_tiles is not None
                     else params.chunk_tiles)
            out, disp = _retrieve_chunked_impl(
                *arrays, n_tiles=index.n_tiles, chunk_tiles=ct,
                use_kernel=use_kernel, fused=traversal == "chunked_fused",
                **statics)
    with scope("device_wait"):
        out, disp = jax.tree_util.tree_map(np.asarray, (out, disp))
    with scope("finish"):
        gv, gi, lv, li, rv, ri, st = out
        stats = dict(zip(STAT_KEYS, st.T))
        b = q_terms.shape[0]
        stats["n_tiles"] = np.full(b, index.n_tiles, np.float32)
        if disp is not None:
            stats["chunks_dispatched"] = disp
            stats["n_chunks"] = np.full(b, -(-index.n_tiles // ct),
                                        np.float32)
        return RetrievalResult(ids=index.to_orig(ri), scores=rv,
                               global_ids=index.to_orig(gi),
                               local_ids=index.to_orig(li), stats=stats)


# ---------------------------------------------------------------------------
# Sequential mode: host tile loop with physical skipping (latency benchmarks).
# ---------------------------------------------------------------------------

@jax.jit
def _plan_with_bounds(qt, qwb, qwl, sigma_b, sigma_l,
                      tile_max_b, tile_max_l, alpha):
    """Planner entry for the host loop: plan + per-tile upper bounds."""
    plan = plan_query(qt, qwb, qwl, sigma_b, sigma_l, alpha)
    ub = tile_upper_bounds(plan, tile_max_b, tile_max_l, alpha)
    return plan, ub


@partial(jax.jit, static_argnames=("k", "kq", "pad_len", "tile_size",
                                   "bound_mode", "gather_kind"))
def _tile_step_jit(gt, tile_max_b, tile_max_l,
                   plan, carry, tile, alpha, beta, gamma, factor,
                   *, k, kq, pad_len, tile_size, bound_mode,
                   gather_kind="fp32"):
    idx_arrays = (gt, tile_max_b, tile_max_l)
    return _tile_step(idx_arrays, plan, carry, tile,
                      alpha, beta, gamma, factor, k=k, kq=kq, pad_len=pad_len,
                      tile_size=tile_size, bound_mode=bound_mode,
                      gather_kind=gather_kind)


def retrieve_sequential(index: BlockedImpactIndex, q_terms, qw_b, qw_l,
                        params: TwoLevelParams,
                        warmup: bool = True,
                        k: int | None = None) -> RetrievalResult:
    """Host-driven per-query traversal with physical tile skipping + timing.

    Mirrors the paper's single-threaded CPU latency regime: skipped tiles
    cost nothing (the gather/score call is never issued). Planning runs
    through the same ``core.plan`` functions as the batched engine; only
    the skip *decision* is evaluated on host so it can elide work.
    ``k`` is the per-call retrieval depth (legacy ``params.k`` fallback).
    """
    B = len(q_terms)
    k = resolve_k(params, k)
    kq = min(k, index.tile_size)
    alpha, beta, gamma = params.alpha, params.beta, params.gamma
    factor = params.threshold_factor
    args = (jnp.float32(alpha), jnp.float32(beta), jnp.float32(gamma),
            jnp.float32(factor))
    statics = dict(k=k, kq=kq, pad_len=index.pad_len,
                   tile_size=index.tile_size, bound_mode=params.bound_mode,
                   gather_kind=index.gather_kind)
    gt = index.gather_arrays()
    ids = np.full((B, k), -1, np.int32)
    scores = np.full((B, k), -np.inf, np.float32)
    g_ids = np.full((B, k), -1, np.int32)
    l_ids = np.full((B, k), -1, np.int32)
    lat = np.zeros(B, np.float64)
    stat_rows = np.zeros((B, 6), np.float32)

    def run_query(qi, record):
        qt = jnp.asarray(np.asarray(q_terms[qi], dtype=np.int32))
        qwb = jnp.asarray(np.asarray(qw_b[qi], dtype=np.float32))
        qwl = jnp.asarray(np.asarray(qw_l[qi], dtype=np.float32))
        plan, ub_dev = _plan_with_bounds(qt, qwb, qwl,
                                         index.sigma_b, index.sigma_l,
                                         index.tile_max_b, index.tile_max_l,
                                         jnp.float32(alpha))
        ub = np.asarray(ub_dev)
        impact = params.schedule == "impact"
        tile_order = (np.argsort(-ub, kind="stable") if impact
                      else np.arange(index.n_tiles))
        t0 = time.perf_counter()
        carry = _init_carry(k)
        th_gl = -np.inf
        visited = 0
        for tau in tile_order:
            if ub[tau] <= th_gl * factor:  # th_gl=-inf never skips
                if impact:
                    break  # ub descending: every later tile fails too
                continue
            carry = _tile_step_jit(
                gt, index.tile_max_b, index.tile_max_l,
                plan, carry, jnp.int32(tau), *args, **statics)
            th_gl = float(carry[0][-1])
            visited += 1
        carry = jax.tree_util.tree_map(np.asarray, carry)
        dt = (time.perf_counter() - t0) * 1e3
        if record:
            gv, gi, lv, li, rv, ri, st = carry
            ids[qi], scores[qi] = ri, rv
            g_ids[qi], l_ids[qi] = gi, li
            lat[qi] = dt
            stat_rows[qi] = np.concatenate([st, [index.n_tiles]])

    if warmup and B > 0:
        run_query(0, record=False)  # compile outside the timed region
    for qi in range(B):
        run_query(qi, record=True)

    stats = dict(zip(STAT_KEYS + ("n_tiles",), stat_rows.T))
    return RetrievalResult(ids=index.to_orig(ids), scores=scores,
                           global_ids=index.to_orig(g_ids),
                           local_ids=index.to_orig(l_ids), stats=stats,
                           latencies_ms=lat)
