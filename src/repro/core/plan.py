"""Query planner for the 2GTI tile-scan engine — the planner/executor contract.

One planner, three executors. Every traversal mode used to carry its own
copy of the sort/bound/skip logic (the batched vmap engine, the sequential
host loop, and the Pallas-kernel wrapper each re-derived term order, tile
upper bounds and the essential partition). This module is now the single
copy; executors only gather, scatter-accumulate, and merge queues.

Planner responsibilities (this module):
  - **term ordering** — ``plan_query`` presorts query terms ascending by
    alpha-combined list maxima and packages the weighted list maxima
    (``sig_b``/``sig_l``) alongside, as a :class:`QueryPlan`;
  - **tile scheduling** — ``tile_upper_bounds`` gives the per-tile
    alpha-combined global upper bound (the tile-skip test operand) and
    ``tile_schedule`` turns it into a visit order (``docid`` or ``impact``);
  - **per-tile term bounds** — ``term_bounds`` yields ``(m_alpha, m_beta,
    ub_gl)`` under either ``bound_mode`` (``list`` = MaxScore list maxima,
    ``tile`` = block-max tightening);
  - **threshold partitioning** — ``essential_terms`` marks the essential
    suffix given theta_Gl, ``freeze_bounds`` gives the inclusive beta-bound
    prefix sums driving the local freeze test.

Executor responsibilities (``core.traversal`` / ``kernels.guided_score``):
  posting gather, dense scatter, the freeze-loop accumulate, per-tile
  candidate top-k and queue merges. Executors receive ``essential`` and
  ``prefix_beta`` ready-made — neither scorer path sees theta_Gl, whose
  only remaining consumer is the planner-side tile-skip test.

Everything here is pure jnp, shape-static, and vmap / shard_map
compatible: the same functions drive the batched engine, the sequential
host loop (which pulls results back with ``np.asarray``) and the
mesh-sharded executor in ``serve.sharded``.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp


def combine(coef, b, l):
    """The paper's two-weight interpolation: coef * B + (1 - coef) * L."""
    return coef * b + (1.0 - coef) * l


class QueryPlan(NamedTuple):
    """Per-query traversal plan: terms presorted ascending by
    alpha-combined list maxima (the MaxScore partition order)."""
    qt: jax.Array      # [Nq] int32 term ids, sorted order
    qwb: jax.Array     # [Nq] f32 BM25-side query weights, sorted order
    qwl: jax.Array     # [Nq] f32 learned-side query weights, sorted order
    sig_b: jax.Array   # [Nq] f32 query-weighted list maxima (BM25 side)
    sig_l: jax.Array   # [Nq] f32 query-weighted list maxima (learned side)


def plan_query(qt, qwb, qwl, sigma_b, sigma_l, alpha) -> QueryPlan:
    """Sort query terms ascending by alpha-combined list maxima."""
    sig_b = qwb * sigma_b[qt]
    sig_l = qwl * sigma_l[qt]
    order = jnp.argsort(combine(alpha, sig_b, sig_l))
    return QueryPlan(qt[order], qwb[order], qwl[order],
                     sig_b[order], sig_l[order])


def live_terms(qwb, qwl):
    """Query slots that carry weight. A slot with no weight on either side
    is padding: it matches no document (the gathers return it an empty
    run) and no tile holds anything for it."""
    return (qwb != 0) | (qwl != 0)


def _bound_or_empty(plan: QueryPlan, raw_b, raw_l, ub):
    """``ub`` where some live term has a posting in the tile (a nonzero
    tile maximum), else -inf: an empty tile holds no match, so every
    skip test and chunk bound passes it over even before the queues
    fill. ``raw_b``/``raw_l`` are the unweighted tile maxima, [Nq, ...]."""
    live = live_terms(plan.qwb, plan.qwl)
    live = live.reshape(live.shape + (1,) * (raw_b.ndim - 1))
    held = (live & ((raw_b > 0) | (raw_l > 0))).any(0)
    return jnp.where(held, ub, -jnp.inf)


def tile_upper_bounds(plan: QueryPlan, tile_max_b, tile_max_l, alpha):
    """Per-tile alpha-combined global upper bounds: [n_tiles]; -inf for a
    tile that holds no posting of a live query term."""
    raw_b = tile_max_b[plan.qt, :]
    raw_l = tile_max_l[plan.qt, :]
    ub = combine(alpha, plan.qwb[:, None] * raw_b,
                 plan.qwl[:, None] * raw_l).sum(0)
    return _bound_or_empty(plan, raw_b, raw_l, ub)


def tile_schedule(plan: QueryPlan, tile_max_b, tile_max_l, alpha,
                  n_tiles: int, schedule: str):
    """Tile visit order. ``docid`` mirrors DAAT; ``impact`` visits tiles in
    descending global upper bound so thresholds tighten fastest."""
    if schedule == "impact":
        ub = tile_upper_bounds(plan, tile_max_b, tile_max_l, alpha)
        return jnp.argsort(-ub).astype(jnp.int32)
    return jnp.arange(n_tiles, dtype=jnp.int32)


class ChunkSchedule(NamedTuple):
    """Descending-bound tile order folded into static fixed-size chunks —
    the Block-Max-Pruning visit structure (process blocks in descending
    bound order, stop when the next bound clears the threshold) mapped
    onto a shape-static ``lax.while_loop`` carrier."""
    chunks: jax.Array    # [n_chunks, chunk_tiles] int32 tile ids; the
    #                      sentinel ``n_tiles`` pads the tail chunk and is
    #                      force-skipped by the executor (tile_valid False)
    chunk_ub: jax.Array  # [n_chunks] f32 max tile upper bound per chunk
    #                      (-inf for chunks of padding or empty tiles): the early-exit
    #                      test operand. Descending by construction.


def chunk_schedule(plan: QueryPlan, tile_max_b, tile_max_l, alpha,
                   n_tiles: int, chunk_tiles: int,
                   n_real: int | jax.Array | None = None) -> ChunkSchedule:
    """Chunked visit order: sort tiles by descending global upper bound and
    pad into static ``[n_chunks, chunk_tiles]`` groups.

    Because tiles are sorted descending, the per-chunk max bound is the
    bound of the chunk's first tile, and the sequence ``chunk_ub`` is
    itself descending — so the first chunk whose bound fails the theta_Gl
    test proves every later tile fails it too, and the executor may stop.

    ``n_real`` (sharded path): tiles with id >= n_real are shape padding;
    their bound is forced to -inf so they sort last and never keep the
    chunk loop alive. The sentinel id ``n_tiles`` pads the ragged tail.
    """
    ub = tile_upper_bounds(plan, tile_max_b, tile_max_l, alpha)
    if n_real is not None:
        ub = jnp.where(jnp.arange(n_tiles) < n_real, ub, -jnp.inf)
    # Same expression as the ``impact`` tile_schedule: identical tie-break
    # order, which is what makes the chunked scan bit-identical to it.
    order = jnp.argsort(-ub).astype(jnp.int32)
    ub_sorted = ub[order]
    n_chunks = -(-n_tiles // chunk_tiles)
    pad = n_chunks * chunk_tiles - n_tiles
    if pad:
        order = jnp.concatenate(
            [order, jnp.full((pad,), n_tiles, jnp.int32)])
        ub_sorted = jnp.concatenate(
            [ub_sorted, jnp.full((pad,), -jnp.inf, jnp.float32)])
    chunks = order.reshape(n_chunks, chunk_tiles)
    return ChunkSchedule(chunks, ub_sorted.reshape(n_chunks, chunk_tiles).max(1))


def term_bounds(plan: QueryPlan, tile_max_b, tile_max_l, tile,
                alpha, beta, bound_mode: str):
    """Bounds for one tile visit: per-term maxima under both combinations
    plus the tile's global upper bound (the skip-test operand; -inf for
    a tile that holds no posting of a live query term).

    ``bound_mode='list'`` partitions with list-level maxima (paper
    MaxScore); ``'tile'`` with the tile-level block maxima.
    """
    raw_b = tile_max_b[plan.qt, tile]
    raw_l = tile_max_l[plan.qt, tile]
    tm_b = plan.qwb * raw_b
    tm_l = plan.qwl * raw_l
    ub_gl = _bound_or_empty(plan, raw_b, raw_l,
                            combine(alpha, tm_b, tm_l).sum())
    if bound_mode == "tile":
        m_alpha = combine(alpha, tm_b, tm_l)
        m_beta = combine(beta, tm_b, tm_l)
    else:
        m_alpha = combine(alpha, plan.sig_b, plan.sig_l)
        m_beta = combine(beta, plan.sig_b, plan.sig_l)
    return m_alpha, m_beta, ub_gl


def essential_terms(m_alpha, th_gl):
    """Global-level term partition: the suffix whose inclusive prefix bound
    exceeds theta_Gl is essential (bool, sorted term order)."""
    return jnp.cumsum(m_alpha) > th_gl


def freeze_bounds(m_beta):
    """Inclusive prefix sums of the beta-combined bounds: the remaining
    upper bound used by the local freeze test before each term."""
    return jnp.cumsum(m_beta)
