"""Pallas TPU kernels for the guided tile-scoring hot loop (paper core).

Fuses, entirely in VMEM, the per-tile inner computation of the 2GTI
tile-scan engine:

  1. posting scatter -> dense per-term rows: a [P, S_blk] hit mask
     (posting offset == lane docid) selects each posting's weight into
     its docid column and a sublane reduction sums it out (exact f32,
     VPU only),
  2. global-level essential-presence masking,
  3. the descending local-pruning freeze loop (beta-combined bound vs
     theta_Lo) with gated accumulation,
  4. the three hybrid combinations Global/Local/Rank,
  5. the per-slot posting count (the hit mask's column sums), from which
     the traversal reads its presence and postings-touched counters.

One pallas_call scores a chunk of tiles for one query; the grid is
(tile-in-chunk, lane block of ``block_s`` docids). The single-tile entry
points are the chunk kernels at one tile with no skip. The kernels are pure
*executors* in the planner/executor contract (``core.plan``): the essential
partition, freeze bounds and per-tile skip predicate arrive precomputed,
theta_Gl never enters the kernel, and *within* a tile the freeze masks gate
the accumulate.

VMEM per grid cell (Nq=32, P=1024, block_s=512, f32): offs/wb/wl blocks
3 * 128 KiB double-buffered, dense-row scratch 2 * 64 KiB, the [P, S_blk]
select 2 MiB, the [6, S_blk] output block 12 KiB double-buffered; the q8
decode adds its [P, P] prefix-sum select (4 MiB) and [Wp, P] word select
(2 MiB). These grow with P, so the kernels refuse
``pad_len > MAX_PAD_LEN``. ``tests/test_tpu_compile.py`` compiles every
kernel for a v5e at the widths the chip smoke serves and at that bound.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .pallas_env import default_interpret

# Widest posting run per (term, tile) the kernels take: at Nq=32 a v5e
# compiles pad_len 4096 and runs out of VMEM at 8192. pad_len is at most
# the tile size, so indexes built with tiles of <= 4096 docs always fit.
MAX_PAD_LEN = 4096


def _check_pad_len(pad_len: int) -> None:
    if pad_len > MAX_PAD_LEN:
        raise ValueError(
            f"guided_score kernels take pad_len <= {MAX_PAD_LEN}, got "
            f"{pad_len}: build the index with tile_size <= {MAX_PAD_LEN} "
            f"to serve it with the kernel engine")


def _score_lanes(offs_row, wb_row, wl_row, ess, pbeta, lane, dense_b,
                 dense_l, th_lo, beta, *, nq: int):
    """Scatter + freeze passes over one lane block of one tile.

    Accessors: ``offs_row(i)`` -> [1, P] int32 (-1 = padding),
    ``wb_row(i)``/``wl_row(i)`` -> [1, P] f32, ``ess(i)``/``pbeta(i)``
    scalars. Returns ``(sb, sl, survive, alive, n_postings)``, each
    [1, block_s] f32."""
    # Pass 1: scatter postings to dense rows, with the essential presence
    # and the per-slot posting count. A select + sublane sum on the VPU:
    # offsets within a run are distinct, so each column sums at most one
    # posting and the result is exact. (A one-hot MXU matmul is not: at
    # default precision it rounds f32 operands to bf16, 0.3% score error
    # on a v5e, which breaks rank safety. The q8 decode sums the same way.)
    def scatter(i, carry):
        ess_cnt, tot_cnt = carry
        hit = offs_row(i).T == lane                        # [P, S_blk]
        db = jnp.sum(jnp.where(hit, wb_row(i).T, 0.0), axis=0, keepdims=True)
        dl = jnp.sum(jnp.where(hit, wl_row(i).T, 0.0), axis=0, keepdims=True)
        cnt = jnp.sum(hit.astype(jnp.float32), axis=0, keepdims=True)
        dense_b[i, :] = db[0]
        dense_l[i, :] = dl[0]
        return ess_cnt + ess(i) * cnt, tot_cnt + cnt
    zero = jnp.zeros(lane.shape, jnp.float32)
    ess_cnt, tot_cnt = jax.lax.fori_loop(0, nq, scatter, (zero, zero))
    survive = (ess_cnt > 0).astype(jnp.float32)

    # Pass 2: descending freeze loop (local level). Unrolled: Mosaic
    # refuses a rolled loop whose [1, S_blk] carries start as constant
    # splats ("Invalid relayout ... replicated in destination").
    def freeze(j, carry):
        i = nq - 1 - j
        sb, sl, alive = carry
        l_part = beta * sb + (1.0 - beta) * sl
        ok = jnp.where(ess(i) > 0, 1.0,
                       (l_part + pbeta(i) > th_lo).astype(jnp.float32))
        alive = alive * ok
        gate = survive * alive
        sb = sb + gate * dense_b[i, :][None, :]
        sl = sl + gate * dense_l[i, :][None, :]
        return sb, sl, alive
    sb, sl, alive = jax.lax.fori_loop(
        0, nq, freeze, (zero, zero, zero + 1.0), unroll=True)
    return sb, sl, survive, alive, tot_cnt


def _write_out(out_ref, scal_ref, sb, sl, survive, alive, tot_cnt):
    """Rows: Global, Local, RankScore, eval mask, rank mask and the
    per-slot posting count (the traversal's stats source)."""
    alpha, beta, gamma = scal_ref[0, 1], scal_ref[0, 2], scal_ref[0, 3]
    out_ref[0, :] = (alpha * sb + (1.0 - alpha) * sl)[0]
    out_ref[1, :] = (beta * sb + (1.0 - beta) * sl)[0]
    out_ref[2, :] = (gamma * sb + (1.0 - gamma) * sl)[0]
    out_ref[3, :] = (survive * alive)[0]
    out_ref[4, :] = survive[0]
    out_ref[5, :] = tot_cnt[0]


def _lane_iota(block_s: int):
    base = pl.program_id(1) * block_s
    return base + jax.lax.broadcasted_iota(jnp.int32, (1, block_s), 1)


# lane blocks must run in order: the q8 decode at lane block 0 fills the
# scratch that the tile's later lane blocks read
_SEQUENTIAL = pltpu.CompilerParams(
    dimension_semantics=("arbitrary", "arbitrary"))


def _chunk_kernel(scal_ref, ess_ref, pbeta_ref, skip_ref,
                  offs_ref, wb_ref, wl_ref, out_ref, dense_b, dense_l,
                  *, nq: int, block_s: int):
    """One grid cell = (tile-in-chunk, lane block). The per-tile skip
    predicate lives in SMEM and gates the scatter + freeze passes via
    ``pl.when`` — a skipped tile costs a predicate read and one zero-fill
    instead of the full scatter and freeze loop, which is what makes
    chunk-level skipping *real* work elision inside a single pallas_call.
    """
    c = pl.program_id(0)
    lane = _lane_iota(block_s)
    # Skipped tiles publish all-zero scores and masks: zero masks mean no
    # candidate survives, so the caller's queue merge is a no-op for them.
    out_ref[...] = jnp.zeros_like(out_ref)

    @pl.when(skip_ref[0, c] == 0)
    def _score():
        parts = _score_lanes(
            lambda i: offs_ref[pl.ds(i, 1), :],
            lambda i: wb_ref[pl.ds(i, 1), :],
            lambda i: wl_ref[pl.ds(i, 1), :],
            lambda i: ess_ref[c, i], lambda i: pbeta_ref[c, i],
            lane, dense_b, dense_l, scal_ref[0, 0], scal_ref[0, 2], nq=nq)
        _write_out(out_ref, scal_ref, *parts)


def _smem():
    return pl.BlockSpec(memory_space=pltpu.SMEM)


def _scalars(th_lo, alpha, beta, gamma):
    # SMEM operands are kept >= 2-D: under vmap (the batched traversal)
    # Pallas adds a squeezed batch block dim, and a 1-D operand's block
    # would then violate the TPU rule that the last two block dims equal
    # the array's or divide (8, 128)
    return jnp.stack([th_lo, alpha, beta, gamma]).astype(jnp.float32)[None]


@functools.partial(jax.jit, static_argnames=("tile_size", "block_s",
                                             "interpret"))
def guided_score_chunk(offs, wb, wl, essential, prefix_beta, skip, th_lo,
                       alpha, beta, gamma, *, tile_size: int,
                       block_s: int = 512, interpret: bool | None = None):
    """Score a whole chunk of tiles for one query in one ``pallas_call``.

    Grid = (chunk_tiles, lane blocks): per-tile dispatch overhead is
    amortized over the chunk and the per-tile ``skip`` predicate (int32,
    [C]; nonzero = skip) turns bound-failing tiles into near-free grid
    cells. Inputs are chunk-stacked: offs/wb/wl [C, Nq, P], essential /
    prefix_beta [C, Nq] (per-tile planner outputs derived from the
    *chunk-start* thresholds — within the chunk that only loosens pruning,
    so rank-safe configs stay exact). Returns [C, 6, tile_size]: Global,
    Local, RankScore, eval mask, rank mask and the per-slot posting count
    (all zero for a skipped tile).

    ``interpret=None`` resolves via :func:`pallas_env.default_interpret`:
    native lowering on TPU backends, the Python interpreter elsewhere.
    """
    if interpret is None:
        interpret = default_interpret()
    n_chunk, nq, p = offs.shape
    _check_pad_len(p)
    block_s = min(block_s, tile_size)
    assert tile_size % block_s == 0
    scal = _scalars(th_lo, alpha, beta, gamma)
    rows = pl.BlockSpec((None, nq, p), lambda c, s: (c, 0, 0))
    return pl.pallas_call(
        functools.partial(_chunk_kernel, nq=nq, block_s=block_s),
        grid=(n_chunk, tile_size // block_s),
        in_specs=[_smem(), _smem(), _smem(), _smem(),  # scal, ess, pbeta, skip
                  rows, rows, rows],                   # offs, wb, wl
        out_specs=pl.BlockSpec((None, 6, block_s), lambda c, s: (c, 0, s)),
        out_shape=jax.ShapeDtypeStruct((n_chunk, 6, tile_size), jnp.float32),
        scratch_shapes=[pltpu.VMEM((nq, block_s), jnp.float32),
                        pltpu.VMEM((nq, block_s), jnp.float32)],
        compiler_params=_SEQUENTIAL,
        interpret=interpret,
        name="guided_score_chunk",
    )(scal, essential.astype(jnp.float32), prefix_beta.astype(jnp.float32),
      skip.astype(jnp.int32)[None], offs, wb, wl)


def guided_score_tile(offs, wb, wl, essential, prefix_beta, th_lo,
                      alpha, beta, gamma, *, tile_size: int,
                      block_s: int = 512, interpret: bool | None = None):
    """Score one (query, tile) pair: ``guided_score_chunk`` on a
    one-tile chunk. Returns [6, tile_size]."""
    return guided_score_chunk(
        offs[None], wb[None], wl[None], essential[None], prefix_beta[None],
        jnp.zeros((1,), jnp.int32), th_lo, alpha, beta, gamma,
        tile_size=tile_size, block_s=block_s, interpret=interpret)[0]


# ---------------------------------------------------------------------------
# Decode-in-kernel variants for the compressed index (q8 gather kind).
#
# Inputs arrive *undecoded* (``repro.index.gather_tile_q_raw``): packed
# delta words, raw uint8 impact codes, per-row run metadata. Lane block 0
# of each tile delta-decodes the offsets and dequantizes both impact
# channels once into VMEM scratch — TPU grid cells run sequentially and
# scratch persists, so later lane blocks reuse the decoded rows. The
# gather is memory-bound, so the decode rides otherwise-idle compute:
#
#   gap_j   = (words[bitpos >> 5] >> (bitpos & 31)) & (2^w - 1)
#             via an int32 select-and-sum word gather over [Wp, P],
#   offs_j  = first + sum_{i <= j} (gap_i + 1)   (int32 prefix sum as a
#             select against the a <= b triangle, summed over a),
#   w_j     = (zero + scale * q_j) * qw           (<= exact tile max * qw
#             by codec construction, so planner bounds stay valid).
#
# The output rows are the fp32 kernel's, so the caller reads the per-slot
# posting count without a second decode.
# ---------------------------------------------------------------------------


def _decode_rows(offs_s, wb_s, wl_s, meta_i, meta_f, qw, words, qb, ql,
                 *, nq: int, pad_len: int, wp: int):
    """Decode all ``nq`` rows of one tile into the scratch buffers.

    Accessors: ``meta_i(r, i)``/``meta_f(r, i)``/``qw(r, i)`` scalar
    reads, ``words(i)`` -> [1, Wp] int32, ``qb(i)``/``ql(i)`` -> [1, P]
    f32 raw codes."""
    j = jax.lax.broadcasted_iota(jnp.int32, (1, pad_len), 1)
    word_iota = jax.lax.broadcasted_iota(jnp.int32, (wp, pad_len), 0)
    # inclusive prefix-sum mask: tri[a, b] iff a <= b
    tri = (jax.lax.broadcasted_iota(jnp.int32, (pad_len, pad_len), 0)
           <= jax.lax.broadcasted_iota(jnp.int32, (pad_len, pad_len), 1))

    def dec(i, _):
        cnt_i = meta_i(0, i)
        first_i = meta_i(1, i)
        w_i = meta_i(2, i)
        bitpos = jnp.maximum(j - 1, 0) * w_i            # value idx = j - 1
        widx = jnp.minimum(bitpos >> 5, wp - 1)         # [1, P]
        word_j = jnp.sum(jnp.where(word_iota == widx, words(i).T, 0),
                         axis=0, keepdims=True)         # [1, P] int32
        gap = (jax.lax.shift_right_logical(word_j, bitpos & 31)
               & ((1 << w_i) - 1))                      # [1, P]
        contrib = jnp.where(j == 0, first_i, gap + 1)
        offs = jnp.sum(jnp.where(tri, contrib.T, 0), axis=0, keepdims=True)
        valid = j < cnt_i
        offs_s[pl.ds(i, 1), :] = jnp.where(valid, offs, -1)
        vmask = valid.astype(jnp.float32)
        wb_s[pl.ds(i, 1), :] = ((meta_f(0, i) + meta_f(1, i) * qb(i))
                                * vmask * qw(0, i))
        wl_s[pl.ds(i, 1), :] = ((meta_f(2, i) + meta_f(3, i) * ql(i))
                                * vmask * qw(1, i))
        return 0
    jax.lax.fori_loop(0, nq, dec, 0)


def _chunk_kernel_q(scal_ref, ess_ref, pbeta_ref, skip_ref, meta_i_ref,
                    meta_f_ref, qw_ref, words_ref, qb_ref, ql_ref, out_ref,
                    dense_b, dense_l, offs_s, wb_s, wl_s,
                    *, nq: int, block_s: int, pad_len: int, wp: int):
    """Chunked decode-in-kernel scoring. The grid iterates lane blocks
    innermost, so decoding tile c's rows at lane block 0 leaves the
    scratch valid for the remaining lane blocks of the same tile. Skipped
    tiles publish zeros and skip both the decode and the score passes."""
    c = pl.program_id(0)
    lane = _lane_iota(block_s)
    out_ref[...] = jnp.zeros_like(out_ref)

    @pl.when((skip_ref[0, c] == 0) & (pl.program_id(1) == 0))
    def _decode():
        _decode_rows(offs_s, wb_s, wl_s,
                     lambda r, i: meta_i_ref[c, r, i],
                     lambda r, i: meta_f_ref[c, r, i],
                     lambda r, i: qw_ref[r, i],
                     lambda i: words_ref[pl.ds(i, 1), :],
                     lambda i: qb_ref[pl.ds(i, 1), :],
                     lambda i: ql_ref[pl.ds(i, 1), :],
                     nq=nq, pad_len=pad_len, wp=wp)

    @pl.when(skip_ref[0, c] == 0)
    def _score():
        parts = _score_lanes(
            lambda i: offs_s[pl.ds(i, 1), :],
            lambda i: wb_s[pl.ds(i, 1), :],
            lambda i: wl_s[pl.ds(i, 1), :],
            lambda i: ess_ref[c, i], lambda i: pbeta_ref[c, i],
            lane, dense_b, dense_l, scal_ref[0, 0], scal_ref[0, 2], nq=nq)
        _write_out(out_ref, scal_ref, *parts)


@functools.partial(jax.jit, static_argnames=("tile_size", "pad_len",
                                             "block_s", "interpret"))
def guided_score_chunk_q(words, qb_row, ql_row, meta_i, meta_f, qw_b, qw_l,
                         essential, prefix_beta, skip, th_lo,
                         alpha, beta, gamma, *, tile_size: int, pad_len: int,
                         block_s: int = 512, interpret: bool | None = None):
    """Chunked decode-in-kernel scoring on the compressed index.

    Chunk-stacked raw inputs (words [C, Nq, Wp], codes [C, Nq, P], meta_i
    [C, 3, Nq], meta_f [C, 4, Nq]) plus the per-term query weights
    (applied after dequantization, preserving the fp32 path's
    ``fl(dequant) * qw <= fl(tile_max * qw)`` bound); per-tile planner
    inputs as ``guided_score_chunk``. Returns [C, 6, tile_size], the rows
    of ``guided_score_chunk``."""
    if interpret is None:
        interpret = default_interpret()
    n_chunk, nq, wp = words.shape
    _check_pad_len(pad_len)
    block_s = min(block_s, tile_size)
    assert tile_size % block_s == 0
    scal = _scalars(th_lo, alpha, beta, gamma)
    qw = jnp.stack([qw_b, qw_l]).astype(jnp.float32)          # [2, Nq]
    kern = functools.partial(_chunk_kernel_q, nq=nq, block_s=block_s,
                             pad_len=pad_len, wp=wp)
    return pl.pallas_call(
        kern,
        grid=(n_chunk, tile_size // block_s),
        in_specs=[_smem(), _smem(), _smem(), _smem(),  # scal, ess, pbeta, skip
                  _smem(), _smem(), _smem(),           # meta_i, meta_f, qw
                  pl.BlockSpec((None, nq, wp), lambda c, s: (c, 0, 0)),
                  pl.BlockSpec((None, nq, pad_len), lambda c, s: (c, 0, 0)),
                  pl.BlockSpec((None, nq, pad_len), lambda c, s: (c, 0, 0))],
        out_specs=pl.BlockSpec((None, 6, block_s), lambda c, s: (c, 0, s)),
        out_shape=jax.ShapeDtypeStruct((n_chunk, 6, tile_size), jnp.float32),
        scratch_shapes=[pltpu.VMEM((nq, block_s), jnp.float32),
                        pltpu.VMEM((nq, block_s), jnp.float32),
                        pltpu.VMEM((nq, pad_len), jnp.int32),
                        pltpu.VMEM((nq, pad_len), jnp.float32),
                        pltpu.VMEM((nq, pad_len), jnp.float32)],
        compiler_params=_SEQUENTIAL,
        interpret=interpret,
        name="guided_score_chunk_q",
    )(scal, essential.astype(jnp.float32), prefix_beta.astype(jnp.float32),
      skip.astype(jnp.int32)[None], meta_i.astype(jnp.int32),
      meta_f.astype(jnp.float32), qw, words, qb_row, ql_row)


def guided_score_tile_q(words, qb_row, ql_row, meta_i, meta_f, qw_b, qw_l,
                        essential, prefix_beta, th_lo, alpha, beta, gamma,
                        *, tile_size: int, pad_len: int, block_s: int = 512,
                        interpret: bool | None = None):
    """Decode-in-kernel scoring of one (query, tile) pair on the
    compressed index: ``guided_score_chunk_q`` on a one-tile chunk.
    Returns [6, tile_size]; inputs are the raw rows from
    ``repro.index.gather_tile_q_raw``."""
    return guided_score_chunk_q(
        words[None], qb_row[None], ql_row[None], meta_i[None], meta_f[None],
        qw_b, qw_l, essential[None], prefix_beta[None],
        jnp.zeros((1,), jnp.int32), th_lo, alpha, beta, gamma,
        tile_size=tile_size, pad_len=pad_len, block_s=block_s,
        interpret=interpret)[0]
