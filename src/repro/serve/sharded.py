"""Mesh-sharded retrieval: per-shard tile scans + collective top-k merge.

The index is partitioned into contiguous tile ranges (``core.shard_plan``)
laid out on a one-axis device mesh. Every shard runs the *same* executor
step as the single-device engine (``core.traversal._tile_step``, planner
from ``core.plan``) over its own tiles under ``shard_map``, carrying
shard-local top-k queues; the final queues are ring-all-gathered
(``dist.collectives.ring_gather_stack``) and merged with one stable top-k
per queue. Stacking the gathered queues in shard order before the merge
preserves the single-device stable-tie discipline: with the ``docid``
schedule the concatenation enumerates candidates in exactly the global
tile order, so for rank-safe configurations (alpha = beta = gamma) the
merged Q_Rk is bit-identical to ``retrieve_batched`` — ids, scores and
tie-breaks. Guided (rank-unsafe) configurations prune against thresholds
whose trajectory depends on traversal order, so a shard's looser local
theta can keep boundary docs the sequential traversal froze; heads agree,
tails may differ within the usual guided tolerance.

Threshold exchange (``exchange_every``): every E tiles the shards
all-gather their Global queues and set a shared floor theta — the k-th
best Global score across the union, i.e. the *exact* global theta at that
point — so subsequent tile skips prune against the global queue rather
than the local one. Thresholds only tighten, so the floor is always safe.

Two execution paths share every formula:

  - ``mesh`` path: ``shard_map`` over a mesh axis, ring-collective merge —
    the multi-device deployment (and the 8-fake-device slow-lane test);
  - emulation path (``mesh=None``): ``vmap`` over the stacked shard axis
    with the identical merge math — runs any shard count on one device
    and is bit-identical to the mesh path, which is what the fast-lane
    parity tests pin down.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from ..core.plan import chunk_schedule, plan_query, tile_schedule
from ..core.shard_plan import ShardedImpactIndex
from ..core.traversal import (STAT_KEYS, RetrievalResult, _chunk_scan,
                              _chunk_while, _init_carry, _tile_step)
from ..core.twolevel import TwoLevelParams, resolve_k
from ..dist.collectives import ring_gather_stack
from .engine import RetrievalServer, ServerConfig


def make_shard_mesh(n_shards: int, axis_name: str = "shard"):
    """One-axis mesh over the first ``n_shards`` local devices."""
    devs = jax.devices()
    if len(devs) < n_shards:
        raise ValueError(
            f"need {n_shards} devices for a {n_shards}-shard mesh, have "
            f"{len(devs)} (set XLA_FLAGS=--xla_force_host_platform_device_"
            f"count={n_shards} before jax initializes, or pass mesh=None "
            f"for the single-device emulation path)")
    return jax.sharding.Mesh(np.array(devs[:n_shards]), (axis_name,))


def _merge_stacked(vals, ids, k: int):
    """Merge shard-stacked queues [n, B, k] -> [B, k], shard-order stable."""
    n, b, kk = vals.shape
    v = jnp.moveaxis(vals, 0, 1).reshape(b, n * kk)
    i = jnp.moveaxis(ids, 0, 1).reshape(b, n * kk)
    top, idx = jax.lax.top_k(v, k)
    return top, jnp.take_along_axis(i, idx, axis=1)


def _global_theta(gv, k: int):
    """k-th best Global score across the union of shard queues: [n,B,k]->[B]."""
    n, b, kk = gv.shape
    v = jnp.moveaxis(gv, 0, 1).reshape(b, n * kk)
    return jax.lax.top_k(v, k)[0][:, -1]


def _fold_schedule(tiles, tiles_per_shard: int, exchange_every: int):
    """Reshape a tile order [..., T] into exchange rounds [..., C, E].

    E is the exchange period (the whole schedule when exchange is off).
    The tail round is padded with the sentinel tile ``tiles_per_shard``:
    it is >= every shard's ``n_real``, so ``_tile_step`` force-skips it
    (``tile_valid`` False) and it touches no queue, stat, or gather —
    every round gets the same static length and the round loop can be a
    single ``lax.scan`` instead of unrolled segments.
    """
    t = tiles.shape[-1]
    period = exchange_every if 0 < exchange_every < t else t
    n_rounds = -(-t // period)
    pad = n_rounds * period - t
    if pad:
        tiles = jnp.concatenate(
            [tiles, jnp.full(tiles.shape[:-1] + (pad,), tiles_per_shard,
                             jnp.int32)], axis=-1)
    return tiles.reshape(tiles.shape[:-1] + (n_rounds, period))


def _plan_shard(tm_b, tm_l, sigma_b, sigma_l, q_terms, qw_b, qw_l, alpha,
                *, tiles_per_shard, schedule):
    """Batched planner for one shard: plans [B, ...], tile order [B, T]."""
    def one(qt, qwb, qwl):
        plan = plan_query(qt, qwb, qwl, sigma_b, sigma_l, alpha)
        tiles = tile_schedule(plan, tm_b, tm_l, alpha,
                              tiles_per_shard, schedule)
        return plan, tiles
    return jax.vmap(one)(q_terms, qw_b, qw_l)


def _plan_shard_chunked(tm_b, tm_l, sigma_b, sigma_l, q_terms, qw_b, qw_l,
                        alpha, n_real, *, tiles_per_shard, chunk_tiles):
    """Chunked planner for one shard: plans [B, ...] plus the descending
    chunk order [B, n_chunks, C] / bounds [B, n_chunks]. Shape-padding
    tiles (id >= ``n_real``) get -inf bounds so they sort last and never
    keep the chunk loop alive; the sentinel ``tiles_per_shard`` pads the
    ragged tail chunk."""
    def one(qt, qwb, qwl):
        plan = plan_query(qt, qwb, qwl, sigma_b, sigma_l, alpha)
        sched = chunk_schedule(plan, tm_b, tm_l, alpha, tiles_per_shard,
                               chunk_tiles, n_real)
        return plan, sched
    return jax.vmap(one)(q_terms, qw_b, qw_l)


def _fold_chunk_rounds(chunks, chunk_ub, tiles_per_shard: int,
                       exchange_every: int, chunk_tiles: int):
    """Fold a chunk order [..., n_chunks, C] into exchange rounds
    [..., n_rounds, per_round, C] (+ bounds [..., n_rounds, per_round]).

    The exchange period is counted in tiles (as for the full scan) and
    rounded up to whole chunks; the tail round is padded with all-sentinel
    chunks (bound -inf) so the round loop stays a single ``lax.scan``.
    """
    n_chunks = chunks.shape[-2]
    if 0 < exchange_every:
        per_round = min(max(1, -(-exchange_every // chunk_tiles)), n_chunks)
    else:
        per_round = n_chunks
    n_rounds = -(-n_chunks // per_round)
    pad = n_rounds * per_round - n_chunks
    if pad:
        chunks = jnp.concatenate(
            [chunks, jnp.full(chunks.shape[:-2] + (pad, chunks.shape[-1]),
                              tiles_per_shard, jnp.int32)], axis=-2)
        chunk_ub = jnp.concatenate(
            [chunk_ub, jnp.full(chunk_ub.shape[:-1] + (pad,), -jnp.inf,
                                jnp.float32)], axis=-1)
    chunks = chunks.reshape(
        chunks.shape[:-2] + (n_rounds, per_round, chunks.shape[-1]))
    chunk_ub = chunk_ub.reshape(chunk_ub.shape[:-1] + (n_rounds, per_round))
    return chunks, chunk_ub


def _chunk_round(idx_arrays, n_real, plans, chunks_round, ub_round,
                 carries, disp, th_floor,
                 alpha, beta, gamma, factor, *, statics):
    """Advance all queries of one shard over one round of chunks with a
    real early exit — the shared ``core.traversal._chunk_while`` loop
    over per-query ``_chunk_scan`` steps, with the exchanged global
    theta as the threshold floor."""
    def step_one(plan, tiles_i, carry, floor):
        return _chunk_scan(idx_arrays, plan, carry, tiles_i,
                           alpha, beta, gamma, factor, n_real,
                           th_floor=floor, **statics)

    def advance(i, carries):
        tiles_i = jax.lax.dynamic_index_in_dim(chunks_round, i, 1, False)
        return jax.vmap(step_one)(plans, tiles_i, carries, th_floor)

    return _chunk_while(advance, ub_round, carries, disp, th_floor, factor)


def _scan_chunk(idx_arrays, n_real, plans, tiles_chunk, carries, th_floor,
                alpha, beta, gamma, factor, *, statics):
    """Advance all queries of one shard over a chunk of its tile order.

    ``n_real`` is the shard's real tile count: shape-padding tiles (local
    index >= n_real) are force-skipped so they touch no queue or stat."""
    def one(plan, tiles_q, carry, floor):
        def step(c, tile):
            return _tile_step(idx_arrays, plan, c, tile,
                              alpha, beta, gamma, factor,
                              th_floor=floor, tile_valid=tile < n_real,
                              **statics), None
        c, _ = jax.lax.scan(step, carry, tiles_q)
        return c
    return jax.vmap(one)(plans, tiles_chunk, carries, th_floor)


def _broadcast_carry(k: int, n: int, b: int):
    return jax.tree_util.tree_map(
        lambda x: jnp.broadcast_to(x, (n, b) + x.shape), _init_carry(k))


def _rebase(ids, base):
    return jnp.where(ids >= 0, ids + base, ids)


@partial(jax.jit, static_argnames=(
    "k", "kq", "pad_len", "tile_size", "bound_mode", "use_kernel",
    "gather_kind", "schedule", "tiles_per_shard", "n_shards",
    "exchange_every", "traversal", "chunk_tiles"))
def _sharded_impl_emulated(gather, tm_b, tm_l, doc_base,
                           n_real, sigma_b, sigma_l, q_terms, qw_b, qw_l,
                           alpha, beta, gamma, factor,
                           *, k, kq, pad_len, tile_size, bound_mode,
                           use_kernel, gather_kind, schedule, tiles_per_shard,
                           n_shards, exchange_every, traversal="full",
                           chunk_tiles=8):
    statics = dict(k=k, kq=kq, pad_len=pad_len, tile_size=tile_size,
                   bound_mode=bound_mode, use_kernel=use_kernel,
                   gather_kind=gather_kind)
    b = q_terms.shape[0]
    carries = _broadcast_carry(k, n_shards, b)
    no_floor = jnp.full((b,), -jnp.inf, jnp.float32)

    if traversal == "chunked":
        planner = partial(_plan_shard_chunked, tiles_per_shard=tiles_per_shard,
                          chunk_tiles=chunk_tiles)
        plans, sched = jax.vmap(
            lambda mb, ml, nr: planner(mb, ml, sigma_b, sigma_l,
                                       q_terms, qw_b, qw_l, alpha, nr)
        )(tm_b, tm_l, n_real)
        # [n_shards, B, R, per, C] -> rounds-first [R, n_shards, B, per, C]
        chunks, chunk_ub = _fold_chunk_rounds(
            sched.chunks, sched.chunk_ub, tiles_per_shard,
            exchange_every, chunk_tiles)
        chunks = jnp.moveaxis(chunks, 2, 0)
        chunk_ub = jnp.moveaxis(chunk_ub, 2, 0)
        disp = jnp.zeros((n_shards, b), jnp.float32)
        round_fn = partial(_chunk_round, statics=statics)

        def run_round(carries, disp, chunks_round, ub_round, floor):
            return jax.vmap(round_fn, in_axes=(0, 0, 0, 0, 0, 0, 0, None,
                                               None, None, None, None))(
                (gather, tm_b, tm_l),
                n_real, plans, chunks_round, ub_round, carries, disp,
                floor, alpha, beta, gamma, factor)

        carries, disp = run_round(carries, disp, chunks[0], chunk_ub[0],
                                  no_floor)
        if chunks.shape[0] > 1:
            def round_step(state, xs):
                carries, disp = state
                floor = _global_theta(carries[0], k)
                return run_round(carries, disp, xs[0], xs[1], floor), None
            (carries, disp), _ = jax.lax.scan(
                round_step, (carries, disp), (chunks[1:], chunk_ub[1:]))
    else:
        disp = None
        planner = partial(_plan_shard, tiles_per_shard=tiles_per_shard,
                          schedule=schedule)
        plans, tiles = jax.vmap(
            lambda mb, ml: planner(mb, ml, sigma_b, sigma_l,
                                   q_terms, qw_b, qw_l, alpha))(tm_b, tm_l)
        scan = partial(_scan_chunk, statics=statics)

        def run_round(carries, tiles_round, floor):
            return jax.vmap(scan, in_axes=(0, 0, 0, 0, 0, None,
                                           None, None, None, None))(
                (gather, tm_b, tm_l),
                n_real, plans, tiles_round, carries, floor,
                alpha, beta, gamma, factor)

        # [n_shards, B, C, E] -> rounds-first [C, n_shards, B, E]
        rounds = jnp.moveaxis(
            _fold_schedule(tiles, tiles_per_shard, exchange_every), 2, 0)
        # round 0 has no exchanged floor; every later round derives the
        # exact global theta from the carries at round *start* — the
        # between-rounds exchange of the old unrolled loop, now inside one
        # lax.scan (two compiled segments total, independent of the round
        # count)
        carries = run_round(carries, rounds[0], no_floor)
        if rounds.shape[0] > 1:
            def round_step(carries, tiles_round):
                floor = _global_theta(carries[0], k)
                return run_round(carries, tiles_round, floor), None
            carries, _ = jax.lax.scan(round_step, carries, rounds[1:])
    gv, gi, lv, li, rv, ri, st = carries
    gi, li, ri = (jax.vmap(_rebase)(i, doc_base) for i in (gi, li, ri))
    gv, gi = _merge_stacked(gv, gi, k)
    lv, li = _merge_stacked(lv, li, k)
    rv, ri = _merge_stacked(rv, ri, k)
    return gv, gi, lv, li, rv, ri, st, disp


@partial(jax.jit, static_argnames=(
    "k", "kq", "pad_len", "tile_size", "bound_mode", "use_kernel",
    "gather_kind", "schedule", "tiles_per_shard", "n_shards",
    "exchange_every", "mesh", "axis_name", "traversal", "chunk_tiles"))
def _sharded_impl_mesh(gather, tm_b, tm_l, doc_base,
                       n_real, sigma_b, sigma_l, q_terms, qw_b, qw_l,
                       alpha, beta, gamma, factor,
                       *, k, kq, pad_len, tile_size, bound_mode, use_kernel,
                       gather_kind, schedule, tiles_per_shard, n_shards,
                       exchange_every, mesh, axis_name, traversal="full",
                       chunk_tiles=8):
    statics = dict(k=k, kq=kq, pad_len=pad_len, tile_size=tile_size,
                   bound_mode=bound_mode, use_kernel=use_kernel,
                   gather_kind=gather_kind)
    scan = partial(_scan_chunk, statics=statics)
    chunked = traversal == "chunked"

    def local_fn(gather, tm_b, tm_l, doc_base, n_real,
                 sigma_b, sigma_l, q_terms, qw_b, qw_l,
                 alpha, beta, gamma, factor):
        # sharded operands arrive with a local leading dim of 1
        idx_arrays = (tuple(a[0] for a in gather), tm_b[0], tm_l[0])
        b = q_terms.shape[0]
        carries = jax.tree_util.tree_map(
            lambda x: jnp.broadcast_to(x, (b,) + x.shape), _init_carry(k))
        no_floor = jnp.full((b,), -jnp.inf, jnp.float32)
        if chunked:
            plans, sched = _plan_shard_chunked(
                tm_b[0], tm_l[0], sigma_b, sigma_l, q_terms, qw_b, qw_l,
                alpha, n_real[0], tiles_per_shard=tiles_per_shard,
                chunk_tiles=chunk_tiles)
            # [B, R, per, C] -> rounds-first [R, B, per, C]; round 0 runs
            # floor-less, later rounds all-gather the exact global theta
            # at round start and early-exit within the round's chunk loop
            chunks, chunk_ub = _fold_chunk_rounds(
                sched.chunks, sched.chunk_ub, tiles_per_shard,
                exchange_every, chunk_tiles)
            chunks = jnp.moveaxis(chunks, 1, 0)
            chunk_ub = jnp.moveaxis(chunk_ub, 1, 0)
            disp = jnp.zeros((b,), jnp.float32)
            round_fn = partial(_chunk_round, statics=statics)
            carries, disp = round_fn(idx_arrays, n_real[0], plans,
                                     chunks[0], chunk_ub[0], carries, disp,
                                     no_floor, alpha, beta, gamma, factor)
            if chunks.shape[0] > 1:
                def round_step(state, xs):
                    carries, disp = state
                    gv_all = ring_gather_stack(carries[0], axis_name,
                                               n_shards)
                    floor = _global_theta(gv_all, k)
                    carries, disp = round_fn(
                        idx_arrays, n_real[0], plans, xs[0], xs[1],
                        carries, disp, floor, alpha, beta, gamma, factor)
                    return (carries, disp), None
                (carries, disp), _ = jax.lax.scan(
                    round_step, (carries, disp), (chunks[1:], chunk_ub[1:]))
            disp_out = disp[None]
        else:
            plans, tiles = _plan_shard(tm_b[0], tm_l[0], sigma_b, sigma_l,
                                       q_terms, qw_b, qw_l, alpha,
                                       tiles_per_shard=tiles_per_shard,
                                       schedule=schedule)
            # [B, C, E] -> rounds-first [C, B, E]; round 0 runs floor-less,
            # later rounds all-gather the exact global theta at round start
            # (same collective count as the old unrolled between-rounds
            # loop)
            rounds = jnp.moveaxis(
                _fold_schedule(tiles, tiles_per_shard, exchange_every), 1, 0)
            carries = scan(idx_arrays, n_real[0], plans, rounds[0],
                           carries, no_floor, alpha, beta, gamma, factor)
            if rounds.shape[0] > 1:
                def round_step(carries, tiles_round):
                    gv_all = ring_gather_stack(carries[0], axis_name,
                                               n_shards)
                    floor = _global_theta(gv_all, k)
                    carries = scan(idx_arrays, n_real[0], plans, tiles_round,
                                   carries, floor, alpha, beta, gamma,
                                   factor)
                    return carries, None
                carries, _ = jax.lax.scan(round_step, carries, rounds[1:])
            disp_out = jnp.zeros((1, b), jnp.float32)
        gv, gi, lv, li, rv, ri, st = carries
        gi, li, ri = (_rebase(i, doc_base[0]) for i in (gi, li, ri))
        merged = []
        for vals, ids in ((gv, gi), (lv, li), (rv, ri)):
            av = ring_gather_stack(vals, axis_name, n_shards)
            ai = ring_gather_stack(ids, axis_name, n_shards)
            merged.append(_merge_stacked(av, ai, k))
        (gv, gi), (lv, li), (rv, ri) = merged
        return gv, gi, lv, li, rv, ri, st[None], disp_out

    sh = P(axis_name)
    sh3 = P(axis_name, None, None)
    rep1, rep2 = P(None), P(None, None)
    scal = P()
    # per-leaf shard specs: every gather leaf is stacked on the shard axis
    gspec = tuple(P(axis_name, *([None] * (a.ndim - 1))) for a in gather)
    f = jax.shard_map(
        local_fn, mesh=mesh,
        in_specs=(gspec, sh3, sh3, sh, sh,
                  rep1, rep1, rep2, rep2, rep2,
                  scal, scal, scal, scal),
        out_specs=(rep2, rep2, rep2, rep2, rep2, rep2, sh3, P(axis_name, None)),
        check_vma=False)
    out = f(gather, tm_b, tm_l, doc_base, n_real,
            sigma_b, sigma_l, q_terms, qw_b, qw_l,
            alpha, beta, gamma, factor)
    gv, gi, lv, li, rv, ri, st, disp = out
    return gv, gi, lv, li, rv, ri, st, (disp if chunked else None)


def shard_retrieve_batched(sharded: ShardedImpactIndex, q_terms, qw_b, qw_l,
                           params: TwoLevelParams, mesh=None,
                           axis_name: str = "shard",
                           use_kernel: bool = False,
                           exchange_every: int = 0,
                           k: int | None = None,
                           traversal: str = "full",
                           chunk_tiles: int | None = None
                           ) -> RetrievalResult:
    """Sharded batched retrieval over a stacked shard index.

    ``mesh=None`` runs the vmap emulation path (any shard count on one
    device, bit-identical to the mesh path); a one-axis mesh whose
    ``axis_name`` size equals ``sharded.n_shards`` runs the collective
    ``shard_map`` path. ``exchange_every=E`` all-gathers the exact global
    theta_Gl every E tiles so shards skip against the global queue; the
    round loop is one ``lax.scan`` over sentinel-padded rounds, so fine
    periods compile at production tile counts. ``k`` is the per-call
    retrieval depth (legacy ``params.k`` fallback).

    ``traversal="chunked"``: each shard scans its tiles in descending
    local-bound chunks of ``chunk_tiles`` (default ``params.chunk_tiles``)
    under a ``lax.while_loop`` that stops at the first bound-failing chunk
    — bit-identical to the ``impact``-schedule full scan per shard
    (shape-padding tiles sort last with -inf bounds and never keep the
    loop alive). With ``exchange_every=E`` the exchange period is rounded
    up to whole chunks and the early exit applies within each round.
    Stats gain ``chunks_dispatched`` / ``n_chunks`` (summed over shards).
    """
    if mesh is not None and mesh.shape[axis_name] != sharded.n_shards:
        raise ValueError(
            f"mesh axis {axis_name!r} has size {mesh.shape[axis_name]} but "
            f"the index has {sharded.n_shards} shards")
    if traversal not in ("full", "chunked"):
        raise ValueError(f"sharded traversal must be 'full' or 'chunked', "
                         f"got {traversal!r}")
    q_terms = jnp.asarray(q_terms, dtype=jnp.int32)
    qw_b = jnp.asarray(qw_b, dtype=jnp.float32)
    qw_l = jnp.asarray(qw_l, dtype=jnp.float32)
    k = resolve_k(params, k)
    kq = min(k, sharded.tile_size)
    ct = int(chunk_tiles if chunk_tiles is not None else params.chunk_tiles)
    kw = dict(k=k, kq=kq, pad_len=sharded.pad_len,
              tile_size=sharded.tile_size, bound_mode=params.bound_mode,
              use_kernel=use_kernel, gather_kind=sharded.gather_kind,
              schedule=params.schedule,
              tiles_per_shard=sharded.tiles_per_shard,
              n_shards=sharded.n_shards, exchange_every=exchange_every,
              traversal=traversal, chunk_tiles=ct)
    args = (sharded.gather,
            sharded.tile_max_b, sharded.tile_max_l, sharded.doc_base,
            sharded.n_real_tiles,
            sharded.sigma_b, sharded.sigma_l, q_terms, qw_b, qw_l,
            jnp.float32(params.alpha), jnp.float32(params.beta),
            jnp.float32(params.gamma), jnp.float32(params.threshold_factor))
    if mesh is None:
        out = _sharded_impl_emulated(*args, **kw)
    else:
        out = _sharded_impl_mesh(*args, **kw, mesh=mesh, axis_name=axis_name)
    gv, gi, lv, li, rv, ri, st, disp = jax.tree_util.tree_map(np.asarray, out)
    agg = st.sum(0)                                    # [B, 5]
    stats = dict(zip(STAT_KEYS, agg.T))
    b = q_terms.shape[0]
    # padding tiles are force-skipped, so the real tile count is the
    # denominator — skip rates stay comparable with retrieve_batched
    stats["n_tiles"] = np.full(b, sharded.n_tiles, np.float32)
    stats["shard_tiles_visited"] = st[:, :, 4].T       # [B, n_shards]
    if disp is not None:
        stats["chunks_dispatched"] = disp.sum(0)       # [B]
        n_chunks = -(-sharded.tiles_per_shard // ct) * sharded.n_shards
        stats["n_chunks"] = np.full(b, n_chunks, np.float32)
        stats["shard_chunks_dispatched"] = disp.T      # [B, n_shards]
    return RetrievalResult(ids=sharded.to_orig(ri), scores=rv,
                           global_ids=sharded.to_orig(gi),
                           local_ids=sharded.to_orig(li), stats=stats)


class ShardedRetrievalServer(RetrievalServer):
    """Deprecated (with :class:`RetrievalServer`): the same shim over
    ``AsyncRetrievalScheduler``, pinned to the mesh-sharded engine. New
    code opens a scheduler with a routing policy whose routes use
    ``engine="sharded"`` (``route(..., engine="sharded", n_shards=N)``).

    Accepts the same queue/batching config; the index is partitioned once
    at construction (inside the ``"sharded"`` registry engine).
    ``mesh=None`` serves through the emulation path."""

    def __init__(self, index, params: TwoLevelParams,
                 cfg: ServerConfig | None = None, *,
                 n_shards: int | None = None, mesh=None,
                 axis_name: str = "shard", use_kernel: bool = False,
                 exchange_every: int = 0, k: int | None = None,
                 traversal: str = "full", chunk_tiles: int | None = None):
        super().__init__(index, params, cfg, engine="sharded", k=k,
                         n_shards=n_shards, mesh=mesh, axis_name=axis_name,
                         use_kernel=use_kernel,
                         exchange_every=exchange_every,
                         traversal=traversal, chunk_tiles=chunk_tiles)
        self.sharded = self.retriever.engine.sharded
        self.mesh = mesh
