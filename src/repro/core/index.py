"""Blocked Impact Index (BII): the TPU-native layout of a merged index.

The docid space is partitioned into tiles of ``tile_size`` documents. For
each (term, tile) we store a CSR pointer into the term's posting run for that
tile, plus tile-granular maxima of both weights (the block-max analogue).
All query-time gathers are static-shaped: a term's postings inside one tile
are one contiguous run of the flat arrays. ``gather_tile`` fetches it as
one ``pad_len``-wide window: the aligned ``FLAT_ROW``-lane rows that hold
the run, gathered from a [rows, ``FLAT_ROW``] view of each flat array, then
shifted to lane 0 and masked to the run's count. Every flat array ends in a
tail of sentinel entries (``INVALID_DOC``, 0.0) that holds the rows of the
last run's window (``with_sentinel_tail``): a row past the end would be
clamped to an earlier one and return another term's postings.

Arrays live as jnp devices arrays; the build is numpy host-side.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from .align import MergedPostings
from .plan import live_terms

INVALID_DOC = np.int32(2**31 - 1)

# Lanes of one row of the [rows, FLAT_ROW] view that ``gather_tile`` fetches
# posting windows through (a TPU vreg's lane count); flat arrays come in
# whole (8, FLAT_ROW) tiles, so the view is the arrays' own memory order.
FLAT_ROW = 128
_FLAT_TILE = 8 * FLAT_ROW


def _window_rows(pad_len: int) -> int:
    """Rows of ``FLAT_ROW`` lanes that hold any ``pad_len``-wide window."""
    return (pad_len + FLAT_ROW - 2) // FLAT_ROW + 1


def flat_len(nnz: int, pad_len: int) -> int:
    """Entries of a flat posting array of ``nnz`` postings with its
    sentinel tail: the rows of any window that starts at or before ``nnz``,
    rounded up to whole (8, ``FLAT_ROW``) tiles."""
    need = nnz + (_window_rows(pad_len) + 1) * FLAT_ROW
    return -(-need // _FLAT_TILE) * _FLAT_TILE


def with_sentinel_tail(docids: np.ndarray, w_b: np.ndarray, w_l: np.ndarray,
                       *, pad_len: int, nnz: int | None = None):
    """The flat posting arrays padded with sentinels (``INVALID_DOC``, 0.0)
    to ``flat_len(nnz, pad_len)`` entries. ``nnz`` defaults to their
    length; ``shard_index`` passes its longest shard's, so every shard
    comes out one length. Every flat array ``gather_tile`` reads is built
    here."""
    n = flat_len(len(docids) if nnz is None else nnz, pad_len)

    def pad(a, fill):
        out = np.full(n, fill, dtype=a.dtype)
        out[:len(a)] = a
        return out
    return (pad(docids, INVALID_DOC), pad(w_b, 0), pad(w_l, 0))


@dataclasses.dataclass
class BlockedImpactIndex:
    n_docs: int
    n_terms: int
    tile_size: int
    n_tiles: int
    pad_len: int          # max postings of one term inside one tile (padded)
    nnz: int              # real postings (the flat arrays hold a tail more)
    # flat postings (term-major, docid-sorted within term), then sentinels
    # (INVALID_DOC, 0.0) up to flat_len(nnz, pad_len): see with_sentinel_tail
    docids: jax.Array     # [flat_len] int32
    w_b: jax.Array        # [flat_len] f32
    w_l: jax.Array        # [flat_len] f32
    # per-(term, tile) structure
    tile_ptr: jax.Array   # [n_terms, n_tiles + 1] int32 (offsets into flat arrays)
    tile_max_b: jax.Array # [n_terms, n_tiles] f32
    tile_max_l: jax.Array # [n_terms, n_tiles] f32
    # list-level maxima
    sigma_b: jax.Array    # [n_terms] f32
    sigma_l: jax.Array    # [n_terms] f32
    # docid remapping (identity unless the index was built with doc_order):
    # orig_of_new[new_id] = original docid, or None for identity.
    orig_of_new: np.ndarray | None = None

    # Static tag dispatched on by the traversal executors (see
    # ``dispatch_gather``). The compressed index reports "q8".
    gather_kind = "fp32"

    def gather_arrays(self) -> tuple[jax.Array, ...]:
        """Posting-side arrays consumed by ``dispatch_gather`` — the
        per-kind payload the executors thread through jit as a pytree."""
        return (self.docids, self.w_b, self.w_l, self.tile_ptr)

    def to_orig(self, ids: np.ndarray) -> np.ndarray:
        """Map internal docids back to original ids (-1 passes through)."""
        ids = np.asarray(ids)
        if self.orig_of_new is None:
            return ids
        safe = np.clip(ids, 0, self.n_docs - 1)
        return np.where(ids < 0, ids, self.orig_of_new[safe]).astype(ids.dtype)


def impact_doc_order(merged: MergedPostings) -> np.ndarray:
    """Docid reordering by descending total learned mass.

    Clusters high-impact documents into few tiles so tile maxima become
    discriminative — the tile-granular analogue of the docid-reassignment
    (BP reordering) used with block-max indexes in PISA. Returns ``order``
    such that new docid ``i`` is original doc ``order[i]``.
    """
    mass = np.zeros(merged.n_docs, dtype=np.float64)
    np.add.at(mass, merged.docids, merged.w_l.astype(np.float64))
    return np.argsort(-mass, kind="stable").astype(np.int32)


def blocked_layout(merged: MergedPostings, tile_size: int = 2048,
                   pad_multiple: int = 8, pad_cap: int | None = None,
                   doc_order: np.ndarray | None = None) -> dict:
    """Host-side tile layout shared by the fp32 and compressed builders.

    Returns a dict of numpy arrays: the (optionally reordered) term-major
    flat postings, ``tile_ptr``/``cnt``, exact per-(term, tile) and
    per-term maxima, and ``pad_len``. ``build_index`` wraps this into
    device arrays; ``repro.index.compress_index`` encodes the same
    layout instead of materializing fp32 postings on device.
    """
    n_docs, n_terms = merged.n_docs, merged.n_terms
    n_tiles = -(-n_docs // tile_size)
    indptr = merged.indptr
    docids = merged.docids
    w_b_arr, w_l_arr = merged.w_b, merged.w_l
    orig_of_new = None
    if doc_order is not None:
        orig_of_new = np.asarray(doc_order, dtype=np.int32)
        new_of_orig = np.empty(n_docs, dtype=np.int32)
        new_of_orig[orig_of_new] = np.arange(n_docs, dtype=np.int32)
        docids = new_of_orig[docids]
        # re-sort each term's postings by the new docid
        term_of = np.repeat(np.arange(n_terms, dtype=np.int64),
                            np.diff(indptr))
        order = np.lexsort((docids, term_of))
        docids = docids[order]
        w_b_arr = w_b_arr[order]
        w_l_arr = w_l_arr[order]

    # tile_ptr[t, tau] = global offset of first posting of term t with
    # docid >= tau * tile_size. searchsorted per term, vectorized over tiles.
    tile_ptr = np.zeros((n_terms, n_tiles + 1), dtype=np.int32)
    bounds = np.arange(n_tiles + 1, dtype=np.int64) * tile_size
    tile_of = (docids.astype(np.int64) // tile_size)
    term_of = np.repeat(np.arange(n_terms, dtype=np.int64), np.diff(indptr))
    # counts[t, tau] = postings of term t in tile tau
    flat = term_of * n_tiles + tile_of
    cnt = np.bincount(flat, minlength=n_terms * n_tiles).reshape(n_terms, n_tiles)
    tile_ptr[:, 1:] = np.cumsum(cnt, axis=1, dtype=np.int64).astype(np.int32)
    tile_ptr += indptr[:n_terms, None].astype(np.int32)
    del bounds

    # per-(term, tile) maxima via max-scatter
    tm_b = np.zeros((n_terms, n_tiles), dtype=np.float32)
    tm_l = np.zeros((n_terms, n_tiles), dtype=np.float32)
    np.maximum.at(tm_b.reshape(-1), flat, w_b_arr)
    np.maximum.at(tm_l.reshape(-1), flat, w_l_arr)

    run_max = int(cnt.max()) if cnt.size else 0
    pad_len = max(pad_multiple, -(-run_max // pad_multiple) * pad_multiple)
    if pad_cap is not None:
        pad_len = min(pad_len, pad_cap)
        if run_max > pad_len:
            raise ValueError(f"pad_cap {pad_cap} < max run {run_max}")

    sigma_b = np.zeros(n_terms, dtype=np.float32)
    sigma_l = np.zeros(n_terms, dtype=np.float32)
    np.maximum.at(sigma_b, term_of, w_b_arr)
    np.maximum.at(sigma_l, term_of, w_l_arr)

    return dict(
        n_docs=n_docs, n_terms=n_terms, tile_size=tile_size, n_tiles=n_tiles,
        pad_len=pad_len, docids=docids.astype(np.int32), w_b=w_b_arr,
        w_l=w_l_arr, tile_ptr=tile_ptr, cnt=cnt, tile_max_b=tm_b,
        tile_max_l=tm_l, sigma_b=sigma_b, sigma_l=sigma_l,
        orig_of_new=orig_of_new)


def build_index(merged: MergedPostings, tile_size: int = 2048,
                pad_multiple: int = 8, pad_cap: int | None = None,
                doc_order: np.ndarray | None = None) -> BlockedImpactIndex:
    """Build the BII from merged postings (host-side numpy).

    ``doc_order`` (optional): permutation; new docid i <- original
    doc_order[i]. Results are mapped back via ``index.to_orig``.

    The flat arrays get a sentinel tail (``with_sentinel_tail``: entries
    ``INVALID_DOC``, 0.0) after the ``nnz`` real postings, so every row of
    ``gather_tile``'s window of the last run exists; ``nnz`` counts the
    real postings only.
    """
    lay = blocked_layout(merged, tile_size, pad_multiple, pad_cap, doc_order)
    flat = with_sentinel_tail(lay["docids"], lay["w_b"], lay["w_l"],
                              pad_len=lay["pad_len"])
    return BlockedImpactIndex(
        n_docs=lay["n_docs"], n_terms=lay["n_terms"], tile_size=tile_size,
        n_tiles=lay["n_tiles"], pad_len=lay["pad_len"],
        nnz=len(lay["docids"]),
        docids=jnp.asarray(flat[0]), w_b=jnp.asarray(flat[1]),
        w_l=jnp.asarray(flat[2]),
        tile_ptr=jnp.asarray(lay["tile_ptr"]),
        tile_max_b=jnp.asarray(lay["tile_max_b"]),
        tile_max_l=jnp.asarray(lay["tile_max_l"]),
        sigma_b=jnp.asarray(lay["sigma_b"]),
        sigma_l=jnp.asarray(lay["sigma_l"]),
        orig_of_new=lay["orig_of_new"])


@partial(jax.jit, static_argnames=("pad_len", "tile_size"))
def gather_tile(docids: jax.Array, w_b: jax.Array, w_l: jax.Array,
                tile_ptr: jax.Array, q_terms: jax.Array, tile: jax.Array,
                qw_b: jax.Array | None = None, qw_l: jax.Array | None = None,
                *, pad_len: int, tile_size: int):
    """Fetch padded posting runs of query terms inside one tile.

    Returns (offs [Nq, P] int32 local doc offsets, -1 where padded;
             wb, wl [Nq, P] f32 zero-padded). ``qw_b``/``qw_l`` (optional,
    [Nq]) scale each term's posting weights by the query weight — the
    executors' query-weighted gather; omitted = raw index weights. Given
    both, a slot with no weight on either side (padding) gets an empty
    run. This is the single gather implementation shared by every
    traversal mode.

    Each (term, tile) run is contiguous in the flat arrays: it starts at
    ``tile_ptr[t, tile]`` and fits in ``_window_rows(pad_len)`` consecutive
    rows of their [rows, ``FLAT_ROW``] view. Those rows come in one row
    gather per array (not ``pad_len`` scalar gathers), are shifted left by
    the start's lane in log2(``FLAT_ROW``) static steps, and are masked to
    the run's count: a fixed handful of device ops whatever the number of
    runs. The flat arrays must hold those rows past the last run
    (``with_sentinel_tail``), or the last runs' rows would be clamped.
    """
    n = docids.shape[0]
    if docids.ndim != 1 or n % _FLAT_TILE or n < flat_len(0, pad_len):
        raise ValueError(f"flat posting arrays of shape {docids.shape} lack "
                         f"the sentinel tail of with_sentinel_tail")
    start = tile_ptr[q_terms, tile]            # [Nq]
    cnt = tile_ptr[q_terms, tile + 1] - start  # [Nq]
    if qw_b is not None and qw_l is not None:
        cnt = jnp.where(live_terms(qw_b, qw_l), cnt, 0)
    lane0 = start % FLAT_ROW
    rows = (start // FLAT_ROW)[:, None] + jnp.arange(
        _window_rows(pad_len), dtype=start.dtype)[None, :]

    def window(a):
        w = jnp.take(a.reshape(n // FLAT_ROW, FLAT_ROW), rows, axis=0,
                     mode="clip").reshape(start.shape[0], -1)
        for b in range(FLAT_ROW.bit_length() - 1):
            k = 1 << b
            w = jnp.where((lane0 & k)[:, None] != 0, w[:, k:], w[:, :-k])
        return w[:, :pad_len]

    mask = jnp.arange(pad_len, dtype=jnp.int32)[None, :] < cnt[:, None]
    d, wb, wl = window(docids), window(w_b), window(w_l)
    offs = jnp.where(mask, d - tile * tile_size, -1).astype(jnp.int32)
    wb = jnp.where(mask, wb, 0.0)
    wl = jnp.where(mask, wl, 0.0)
    if qw_b is not None:
        wb = wb * qw_b[:, None]
    if qw_l is not None:
        wl = wl * qw_l[:, None]
    return offs, wb, wl


def dispatch_gather(kind: str, gt: tuple, q_terms: jax.Array,
                    tile: jax.Array, qw_b: jax.Array | None = None,
                    qw_l: jax.Array | None = None, *, pad_len: int,
                    tile_size: int):
    """Kind-polymorphic tile gather.

    ``kind`` is the index's static ``gather_kind`` ("fp32" | "q8") and
    ``gt`` its ``gather_arrays()`` tuple. Both index types decode to the
    same (offs, wb, wl) padded-run contract, so every executor above
    this call is codec-agnostic. Called inside jit with ``kind`` static.
    """
    if kind == "fp32":
        docids, w_b, w_l, tile_ptr = gt
        return gather_tile(docids, w_b, w_l, tile_ptr, q_terms, tile,
                           qw_b, qw_l, pad_len=pad_len, tile_size=tile_size)
    if kind == "q8":
        from ..index.compressed import gather_tile_q
        return gather_tile_q(gt, q_terms, tile, qw_b, qw_l,
                             pad_len=pad_len, tile_size=tile_size)
    raise ValueError(f"unknown gather kind: {kind!r}")
