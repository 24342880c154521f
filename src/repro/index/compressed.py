"""CompressedImpactIndex: the BII layout with compressed posting storage.

Same tile geometry and planner metadata as ``core.index.BlockedImpactIndex``
— identical ``tile_ptr``, *exact* fp32 per-(term, tile) maxima and list
maxima, same padded-gather contract — but the flat posting arrays are
stored compressed:

  docids   ->  per-run first offset (uint16) + delta-1 gaps bit-packed at a
               per-run width from {1, 2, 4, 8, 16} into uint32 words
               (``pack_ptr`` is the word-granular CSR mirror of
               ``tile_ptr``; every run is word-aligned so shards and
               streamed chunks concatenate without re-packing),
  impacts  ->  uint8 codes with per-run fp16 scale/zero-point, rounded so
               dequantized values never exceed the exact fp32 tile max
               (see ``codec.quantize_runs`` — bounds stay valid, so chunk
               scheduling and theta pruning are byte-identical in *plan*
               to the fp32 index).

Per posting: 4 B docid + 8 B impacts (fp32 BII) vs ~width/8 + 2 B here,
plus per-run metadata amortized over the run — the bytes-per-doc ratio is
recorded by ``benchmarks/million_doc.py``.

Decode happens *inside the gather* (``gather_tile_q``), which feeds the
same ``(offs, wb, wl)`` executor contract as the fp32 gather; the Pallas
kernels get a raw-row variant (``gather_tile_q_raw``) and decode in-VMEM
(``kernels.guided_score.guided_score_tile_q``).
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from ..core.align import MergedPostings
from ..core.index import blocked_layout
from ..core.plan import live_terms
from . import codec


@dataclasses.dataclass
class CompressedImpactIndex:
    n_docs: int
    n_terms: int
    tile_size: int
    n_tiles: int
    pad_len: int
    nnz: int
    # compressed flat postings (term-major, docid-sorted within term)
    packed: jax.Array     # [n_words] uint32 bit-packed delta-1 gaps
    qb: jax.Array         # [nnz] uint8 quantized BM25 impacts
    ql: jax.Array         # [nnz] uint8 quantized learned impacts
    # per-(term, tile) structure
    tile_ptr: jax.Array   # [n_terms, n_tiles + 1] int32 posting offsets
    pack_ptr: jax.Array   # [n_terms, n_tiles + 1] int32 word offsets
    width: jax.Array      # [n_terms, n_tiles] uint8 gap bit width
    first: jax.Array      # [n_terms, n_tiles] uint16 first local offset
    scale_b: jax.Array    # [n_terms, n_tiles] f16
    zero_b: jax.Array     # [n_terms, n_tiles] f16
    scale_l: jax.Array    # [n_terms, n_tiles] f16
    zero_l: jax.Array     # [n_terms, n_tiles] f16
    # exact fp32 bounds — unchanged from the uncompressed index
    tile_max_b: jax.Array
    tile_max_l: jax.Array
    sigma_b: jax.Array
    sigma_l: jax.Array
    orig_of_new: np.ndarray | None = None

    gather_kind = "q8"

    def gather_arrays(self) -> tuple[jax.Array, ...]:
        """Posting-side payload for ``core.index.dispatch_gather``."""
        return (self.packed, self.qb, self.ql, self.tile_ptr, self.pack_ptr,
                self.width, self.first, self.scale_b, self.zero_b,
                self.scale_l, self.zero_l)

    def to_orig(self, ids: np.ndarray) -> np.ndarray:
        """Map internal docids back to original ids (-1 passes through)."""
        ids = np.asarray(ids)
        if self.orig_of_new is None:
            return ids
        safe = np.clip(ids, 0, self.n_docs - 1)
        return np.where(ids < 0, ids, self.orig_of_new[safe]).astype(ids.dtype)

    def nbytes(self) -> dict:
        """Actual on-device bytes per component (+ ``total``)."""
        comp = {}
        for name in ("packed", "qb", "ql", "tile_ptr", "pack_ptr", "width",
                     "first", "scale_b", "zero_b", "scale_l", "zero_l",
                     "tile_max_b", "tile_max_l", "sigma_b", "sigma_l"):
            a = getattr(self, name)
            comp[name] = int(a.size) * a.dtype.itemsize
        comp["total"] = sum(comp.values())
        return comp

    def fp32_nbytes(self) -> int:
        """Bytes of the fp32 ``BlockedImpactIndex`` holding the same
        postings/geometry (docids+w_b+w_l flat arrays, tile_ptr, tile
        maxima, sigmas) — the baseline for the compression ratio."""
        return (self.nnz * 12
                + self.n_terms * (self.n_tiles + 1) * 4
                + self.n_terms * self.n_tiles * 8
                + self.n_terms * 8)

    def save(self, path) -> None:
        """Persist to one ``.npz`` (host copy of every array)."""
        meta = np.array([self.n_docs, self.n_terms, self.tile_size,
                         self.n_tiles, self.pad_len, self.nnz], np.int64)
        arrays = {name: np.asarray(getattr(self, name)) for name in
                  ("packed", "qb", "ql", "tile_ptr", "pack_ptr", "width",
                   "first", "scale_b", "zero_b", "scale_l", "zero_l",
                   "tile_max_b", "tile_max_l", "sigma_b", "sigma_l")}
        if self.orig_of_new is not None:
            arrays["orig_of_new"] = self.orig_of_new
        np.savez(path, meta=meta, **arrays)

    @classmethod
    def load(cls, path) -> "CompressedImpactIndex":
        with np.load(path) as z:
            meta = z["meta"]
            kw = {name: jnp.asarray(z[name]) for name in
                  ("packed", "qb", "ql", "tile_ptr", "pack_ptr", "width",
                   "first", "scale_b", "zero_b", "scale_l", "zero_l",
                   "tile_max_b", "tile_max_l", "sigma_b", "sigma_l")}
            orig = z["orig_of_new"] if "orig_of_new" in z.files else None
        return cls(n_docs=int(meta[0]), n_terms=int(meta[1]),
                   tile_size=int(meta[2]), n_tiles=int(meta[3]),
                   pad_len=int(meta[4]), nnz=int(meta[5]),
                   orig_of_new=orig, **kw)


def encode_runs(loc: np.ndarray, w_b: np.ndarray, w_l: np.ndarray,
                run_of: np.ndarray, cnt_flat: np.ndarray) -> dict:
    """Encode term-major postings grouped into (term, tile) runs.

    loc:      [nnz] tile-local offsets, strictly increasing within a run
    run_of:   [nnz] run id per posting (non-decreasing)
    cnt_flat: [n_runs] postings per run

    Returns numpy arrays: ``packed`` (uint32, runs word-aligned in run-id
    order), ``qb``/``ql`` (uint8, posting order), and per-run ``width``
    (uint8), ``first`` (uint16), ``words`` (int64), scale/zero fp16 pairs.
    Runs are fully self-contained, so concatenating the outputs of
    per-chunk encodes (in global run order) is bit-identical to one
    encode of the whole corpus — the property the streaming builder's
    chunked-vs-oneshot test pins.
    """
    loc = np.asarray(loc, dtype=np.int64)
    run_of = np.asarray(run_of, dtype=np.int64)
    cnt_flat = np.asarray(cnt_flat, dtype=np.int64)
    n_runs = len(cnt_flat)
    nnz = len(loc)
    run_start = np.zeros(n_runs + 1, dtype=np.int64)
    np.cumsum(cnt_flat, out=run_start[1:])
    if int(run_start[-1]) != nnz:
        raise ValueError("cnt_flat does not sum to len(loc)")

    pos = np.arange(nnz, dtype=np.int64) - run_start[run_of]
    is_first = pos == 0
    prev = np.empty(nnz, dtype=np.int64)
    prev[1:] = loc[:-1]
    prev[:1] = 0
    gaps = np.where(is_first, 0, loc - prev - 1)
    if nnz and int(gaps.min()) < 0:
        raise ValueError("run offsets must be strictly increasing")

    enc_mask = ~is_first
    maxv = np.zeros(n_runs, dtype=np.int64)
    np.maximum.at(maxv, run_of[enc_mask], gaps[enc_mask])
    width = codec.choose_width(maxv)
    words = codec.words_for(np.maximum(cnt_flat - 1, 0), width)
    word_start = np.zeros(n_runs + 1, dtype=np.int64)
    np.cumsum(words, out=word_start[1:])
    packed = codec.pack_runs(gaps[enc_mask], run_of[enc_mask],
                             (pos - 1)[enc_mask], width, word_start[:-1])
    total_words = int(word_start[-1])
    if len(packed) < total_words:  # trailing empty runs
        packed = np.concatenate(
            [packed, np.zeros(total_words - len(packed), np.uint32)])

    first = np.zeros(n_runs, dtype=np.int64)
    first[run_of[is_first]] = loc[is_first]
    if n_runs and int(first.max(initial=0)) > 0xFFFF:
        raise ValueError("tile-local offset exceeds uint16; "
                         "tile_size must be <= 65536")

    qb, scale_b, zero_b = codec.quantize_runs(w_b, run_of, n_runs)
    ql, scale_l, zero_l = codec.quantize_runs(w_l, run_of, n_runs)
    return dict(packed=packed, qb=qb, ql=ql, width=width,
                first=first.astype(np.uint16), words=words,
                scale_b=scale_b, zero_b=zero_b,
                scale_l=scale_l, zero_l=zero_l)


def from_encoded_grids(n_docs: int, n_terms: int, tile_size: int,
                       cnt: np.ndarray, words: np.ndarray,
                       packed: np.ndarray, qb: np.ndarray, ql: np.ndarray,
                       width: np.ndarray, first: np.ndarray,
                       scale_b: np.ndarray, zero_b: np.ndarray,
                       scale_l: np.ndarray, zero_l: np.ndarray,
                       tile_max_b: np.ndarray, tile_max_l: np.ndarray,
                       *, pad_multiple: int = 8, pad_cap: int | None = None,
                       orig_of_new: np.ndarray | None = None
                       ) -> CompressedImpactIndex:
    """Assemble the device index from [n_terms, n_tiles] metadata grids
    plus the flat encoded arrays (global term-major run order). Shared by
    the one-shot compressor and the streaming builder's finalize."""
    n_tiles = cnt.shape[1]
    tile_ptr_f = np.zeros(n_terms * n_tiles + 1, dtype=np.int64)
    np.cumsum(cnt.reshape(-1), out=tile_ptr_f[1:])
    tile_ptr = np.empty((n_terms, n_tiles + 1), dtype=np.int32)
    tile_ptr[:, :-1] = tile_ptr_f[:-1].reshape(n_terms, n_tiles)
    tile_ptr[:, -1] = tile_ptr_f[1:].reshape(n_terms, n_tiles)[:, -1]

    pack_ptr_f = np.zeros(n_terms * n_tiles + 1, dtype=np.int64)
    np.cumsum(words.reshape(-1), out=pack_ptr_f[1:])
    pack_ptr = np.empty((n_terms, n_tiles + 1), dtype=np.int32)
    pack_ptr[:, :-1] = pack_ptr_f[:-1].reshape(n_terms, n_tiles)
    pack_ptr[:, -1] = pack_ptr_f[1:].reshape(n_terms, n_tiles)[:, -1]

    run_max = int(cnt.max()) if cnt.size else 0
    pad_len = max(pad_multiple, -(-run_max // pad_multiple) * pad_multiple)
    if pad_cap is not None:
        pad_len = min(pad_len, pad_cap)
        if run_max > pad_len:
            raise ValueError(f"pad_cap {pad_cap} < max run {run_max}")

    return CompressedImpactIndex(
        n_docs=n_docs, n_terms=n_terms, tile_size=tile_size,
        n_tiles=n_tiles, pad_len=pad_len, nnz=int(tile_ptr_f[-1]),
        packed=jnp.asarray(packed, dtype=jnp.uint32),
        qb=jnp.asarray(qb, dtype=jnp.uint8),
        ql=jnp.asarray(ql, dtype=jnp.uint8),
        tile_ptr=jnp.asarray(tile_ptr), pack_ptr=jnp.asarray(pack_ptr),
        width=jnp.asarray(width.reshape(n_terms, n_tiles)),
        first=jnp.asarray(first.reshape(n_terms, n_tiles)),
        scale_b=jnp.asarray(scale_b.reshape(n_terms, n_tiles)),
        zero_b=jnp.asarray(zero_b.reshape(n_terms, n_tiles)),
        scale_l=jnp.asarray(scale_l.reshape(n_terms, n_tiles)),
        zero_l=jnp.asarray(zero_l.reshape(n_terms, n_tiles)),
        tile_max_b=jnp.asarray(tile_max_b), tile_max_l=jnp.asarray(tile_max_l),
        sigma_b=jnp.asarray(tile_max_b.max(axis=1)),
        sigma_l=jnp.asarray(tile_max_l.max(axis=1)),
        orig_of_new=orig_of_new)


def compress_index(merged: MergedPostings, tile_size: int = 2048,
                   pad_multiple: int = 8, pad_cap: int | None = None,
                   doc_order: np.ndarray | None = None
                   ) -> CompressedImpactIndex:
    """One-shot compressed build — same signature as ``core.build_index``
    and the same tile layout (via ``core.index.blocked_layout``), with the
    flat postings encoded instead of stored fp32."""
    lay = blocked_layout(merged, tile_size, pad_multiple, pad_cap, doc_order)
    n_terms, n_tiles = lay["n_terms"], lay["n_tiles"]
    docids = lay["docids"].astype(np.int64)
    tile_of = docids // tile_size
    term_of = np.repeat(np.arange(n_terms, dtype=np.int64),
                        lay["cnt"].sum(axis=1, dtype=np.int64))
    run_of = term_of * n_tiles + tile_of
    loc = docids - tile_of * tile_size
    enc = encode_runs(loc, lay["w_b"], lay["w_l"], run_of,
                      lay["cnt"].reshape(-1))
    g = lambda a: np.asarray(a).reshape(n_terms, n_tiles)
    return from_encoded_grids(
        lay["n_docs"], n_terms, tile_size, lay["cnt"], g(enc["words"]),
        enc["packed"], enc["qb"], enc["ql"], g(enc["width"]), g(enc["first"]),
        g(enc["scale_b"]), g(enc["zero_b"]), g(enc["scale_l"]),
        g(enc["zero_l"]), lay["tile_max_b"], lay["tile_max_l"],
        pad_multiple=pad_multiple, pad_cap=pad_cap,
        orig_of_new=lay["orig_of_new"])


# ---------------------------------------------------------------------------
# Query-time decode (jnp reference path + raw rows for the Pallas kernels)
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("pad_len", "tile_size"))
def gather_tile_q(gt: tuple, q_terms: jax.Array, tile: jax.Array,
                  qw_b: jax.Array | None = None,
                  qw_l: jax.Array | None = None,
                  *, pad_len: int, tile_size: int):
    """Decode-on-gather: the q8 counterpart of ``core.index.gather_tile``.

    Returns the identical (offs [Nq, P] int32 / wb, wl [Nq, P] f32)
    contract: gap j decodes as one word load + shift + mask (widths divide
    32, so no value spans words), offsets come from one cumsum over
    ``first`` and the gaps, impacts dequantize as ``(zero + scale * q)``
    — each <= the exact fp32 tile max by construction — then scale by the
    query weight exactly like the fp32 gather.
    """
    (packed, qb, ql, tile_ptr, pack_ptr, width, first,
     scale_b, zero_b, scale_l, zero_l) = gt
    start = tile_ptr[q_terms, tile]                     # [Nq]
    cnt = tile_ptr[q_terms, tile + 1] - start           # [Nq]
    if qw_b is not None and qw_l is not None:
        cnt = jnp.where(live_terms(qw_b, qw_l), cnt, 0)  # padding slots
    pw = pack_ptr[q_terms, tile]                        # [Nq]
    w = width[q_terms, tile].astype(jnp.int32)          # [Nq]
    f0 = first[q_terms, tile].astype(jnp.int32)         # [Nq]

    j = jnp.arange(pad_len, dtype=jnp.int32)[None, :]   # [1, P]
    bitpos = jnp.maximum(j - 1, 0) * w[:, None]         # value idx = j - 1
    word = jnp.take(packed, pw[:, None] + (bitpos >> 5), mode="clip")
    mask = (jnp.uint32(1) << w.astype(jnp.uint32)) - jnp.uint32(1)
    val = (word >> (bitpos & 31).astype(jnp.uint32)) & mask[:, None]
    contrib = jnp.where(j == 0, f0[:, None], val.astype(jnp.int32) + 1)
    valid = j < cnt[:, None]
    offs = jnp.where(valid, jnp.cumsum(contrib, axis=1), -1).astype(jnp.int32)

    idx = jnp.where(valid, start[:, None] + j, 0)

    def deq(codes, scale, zero):
        z = zero[q_terms, tile].astype(jnp.float32)[:, None]
        s = scale[q_terms, tile].astype(jnp.float32)[:, None]
        v = z + s * jnp.take(codes, idx, mode="clip").astype(jnp.float32)
        return jnp.where(valid, v, 0.0)

    wb = deq(qb, scale_b, zero_b)
    wl = deq(ql, scale_l, zero_l)
    if qw_b is not None:
        wb = wb * qw_b[:, None]
    if qw_l is not None:
        wl = wl * qw_l[:, None]
    return offs, wb, wl


def raw_words_len(pad_len: int) -> int:
    """Packed words needed to cover a run of ``pad_len`` postings: at most
    ``pad_len - 1`` gaps at 16 bits = ceil((pad_len - 1) / 2) words."""
    return max(1, (pad_len + 1) // 2)


@partial(jax.jit, static_argnames=("pad_len",))
def gather_tile_q_raw(gt: tuple, q_terms: jax.Array, tile: jax.Array,
                      qw_b: jax.Array | None = None,
                      qw_l: jax.Array | None = None, *, pad_len: int):
    """Fetch *undecoded* per-term rows for the in-kernel Pallas decode.
    Given both query weights, a padding slot (no weight on either side)
    gets ``cnt`` 0, as in ``gather_tile_q``.

    Returns:
      words   [Nq, Wp] int32 — packed gap words (bitcast from uint32)
      qb_row  [Nq, P]  f32   — raw uint8 impact codes (garbage past cnt;
      ql_row  [Nq, P]  f32     the kernel gates on j < cnt)
      meta_i  [3, Nq]  int32 — rows: cnt, first, width
      meta_f  [4, Nq]  f32   — rows: zero_b, scale_b, zero_l, scale_l
    """
    (packed, qb, ql, tile_ptr, pack_ptr, width, first,
     scale_b, zero_b, scale_l, zero_l) = gt
    start = tile_ptr[q_terms, tile]
    cnt = tile_ptr[q_terms, tile + 1] - start
    if qw_b is not None and qw_l is not None:
        cnt = jnp.where(live_terms(qw_b, qw_l), cnt, 0)
    pw = pack_ptr[q_terms, tile]
    wp = raw_words_len(pad_len)
    widx = pw[:, None] + jnp.arange(wp, dtype=jnp.int32)[None, :]
    words = jax.lax.bitcast_convert_type(
        jnp.take(packed, widx, mode="clip"), jnp.int32)
    j = jnp.arange(pad_len, dtype=jnp.int32)[None, :]
    idx = start[:, None] + j
    qb_row = jnp.take(qb, idx, mode="clip").astype(jnp.float32)
    ql_row = jnp.take(ql, idx, mode="clip").astype(jnp.float32)
    meta_i = jnp.stack([cnt, first[q_terms, tile].astype(jnp.int32),
                        width[q_terms, tile].astype(jnp.int32)])
    meta_f = jnp.stack([zero_b[q_terms, tile].astype(jnp.float32),
                        scale_b[q_terms, tile].astype(jnp.float32),
                        zero_l[q_terms, tile].astype(jnp.float32),
                        scale_l[q_terms, tile].astype(jnp.float32)])
    return words, qb_row, ql_row, meta_i, meta_f
