"""Tile-range sharding of the BlockedImpactIndex for mesh-parallel retrieval.

The docid space is partitioned into ``n_shards`` *contiguous tile ranges*
(tiles are already independent scan units, so a range of them is a fully
self-contained mini-index). For each shard the host build re-packs the
term-major posting runs that fall inside its range, rebases docids to the
shard-local space (docid - shard_start_tile * tile_size) and rebases
``tile_ptr`` into the shard's flat arrays. All shards are padded to one
static shape — ``tiles_per_shard`` tiles, ``max_nnz`` postings — and
stacked on a leading shard axis, so the stack maps directly onto a mesh
axis via ``shard_map`` (or a ``vmap`` emulation on one device).

Both index kinds shard: the fp32 ``BlockedImpactIndex`` and the
``repro.index.CompressedImpactIndex``. The posting payload is carried as
the index's ``gather_arrays()`` tuple with every leaf stacked on the
shard axis (``gather_kind`` tags the layout). Compressed runs need no
value rebase — delta gaps and the per-run first offset are tile-local,
so sharding only re-bases the two CSR pointer grids (``tile_ptr`` at
posting granularity, ``pack_ptr`` at word granularity; runs are
word-aligned, so word spans concatenate without re-packing) and slices
the per-(term, tile) metadata columns.

List-level maxima (``sigma_b``/``sigma_l``) stay *global* and replicated:
every shard must sort query terms in the same order or the MaxScore
partition — and therefore results — would diverge between shard counts.

Padded tiles (when ``n_shards`` does not divide ``n_tiles``) carry zero
postings and zero block maxima; they survive nothing and contribute only
NEG_INF candidates, which lose stable-tie merges against real entries.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from .index import BlockedImpactIndex, with_sentinel_tail


@dataclasses.dataclass
class ShardedImpactIndex:
    """Stacked per-shard view of a blocked index (leading dim = shard)."""
    n_shards: int
    n_docs: int
    n_terms: int
    tile_size: int
    n_tiles: int            # real (unpadded) global tile count
    tiles_per_shard: int    # padded: n_shards * tiles_per_shard >= n_tiles
    pad_len: int
    doc_base: jax.Array     # [n_shards] int32 first internal docid per shard
    n_real_tiles: jax.Array  # [n_shards] int32 real tiles (rest is padding)
    nnz_per_shard: np.ndarray
    # posting payload: the source index's gather_arrays() tuple, every
    # leaf stacked on a leading shard axis and padded to a common shape
    gather: tuple
    gather_kind: str        # "fp32" | "q8" (static; threaded through jit)
    tile_max_b: jax.Array   # [n_shards, n_terms, tiles_per_shard] f32
    tile_max_l: jax.Array   # [n_shards, n_terms, tiles_per_shard] f32
    sigma_b: jax.Array      # [n_terms] f32 — global, replicated
    sigma_l: jax.Array      # [n_terms] f32 — global, replicated
    orig_of_new: np.ndarray | None = None

    def _fp32_leaf(self, i: int) -> jax.Array:
        if self.gather_kind != "fp32":
            raise AttributeError(
                "flat fp32 posting views are only defined for "
                f"gather_kind='fp32' (this index is {self.gather_kind!r})")
        return self.gather[i]

    # fp32 back-compat views (pre-gather-tuple field names)
    @property
    def docids(self) -> jax.Array:
        return self._fp32_leaf(0)

    @property
    def w_b(self) -> jax.Array:
        return self._fp32_leaf(1)

    @property
    def w_l(self) -> jax.Array:
        return self._fp32_leaf(2)

    @property
    def tile_ptr(self) -> jax.Array:
        return self.gather[3]  # same slot in both layouts

    def to_orig(self, ids: np.ndarray) -> np.ndarray:
        """Map internal docids back to original ids (-1 passes through)."""
        ids = np.asarray(ids)
        if self.orig_of_new is None:
            return ids
        safe = np.clip(ids, 0, self.n_docs - 1)
        return np.where(ids < 0, ids, self.orig_of_new[safe]).astype(ids.dtype)


def _csr_shard_gather(h_ptr: np.ndarray, t0: int, t1: int):
    """Span bookkeeping for one shard of a [n_terms, n_tiles+1] CSR grid.

    Returns (flat gather index into the flat payload, rebased local CSR
    of shape [n_terms, t1-t0+1], per-term span lengths)."""
    starts = h_ptr[:, t0].astype(np.int64)
    ends = h_ptr[:, t1].astype(np.int64)
    lens = ends - starts
    total = int(lens.sum())
    out_starts = np.zeros(len(lens) + 1, dtype=np.int64)
    np.cumsum(lens, out=out_starts[1:])
    flat = (np.arange(total, dtype=np.int64)
            - np.repeat(out_starts[:-1], lens) + np.repeat(starts, lens))
    local = (h_ptr[:, t0:t1 + 1].astype(np.int64)
             - starts[:, None] + out_starts[:-1, None]).astype(np.int32)
    return flat, local, out_starts


def _pad_cols(a: np.ndarray, tps: int) -> np.ndarray:
    """Zero-pad a sliced [n_terms, real] metadata grid to tps columns."""
    if a.shape[1] == tps:
        return a
    out = np.zeros((a.shape[0], tps), dtype=a.dtype)
    out[:, :a.shape[1]] = a
    return out


def _pad_ptr(lp: np.ndarray, tps: int) -> np.ndarray:
    """Pad a rebased local CSR to tps+1 cols, repeating the last offset."""
    n_terms, cols = lp.shape
    out = np.empty((n_terms, tps + 1), dtype=np.int32)
    out[:, :cols] = lp
    out[:, cols:] = lp[:, -1:]
    return out


def _pad_flat(a: np.ndarray, n: int, fill=0) -> np.ndarray:
    """Pad one shard's flat q8 leaf to ``n`` entries of ``fill``. (fp32
    shards take ``with_sentinel_tail`` instead, the tail its gather reads.)"""
    out = np.full(n, fill, dtype=a.dtype)
    out[:len(a)] = a
    return out


def shard_index(index, n_shards: int) -> ShardedImpactIndex:
    """Partition ``index`` (fp32 or compressed) into ``n_shards``
    contiguous tile ranges.

    Host-side numpy re-pack; shards are padded to a common static shape so
    the result stacks on a leading shard axis.
    """
    if n_shards < 1:
        raise ValueError(f"n_shards must be >= 1, got {n_shards}")
    kind = index.gather_kind
    n_terms, n_tiles = index.n_terms, index.n_tiles
    tile_size = index.tile_size
    tps = -(-n_tiles // n_shards)  # ceil: padded tiles per shard

    h_ptr = np.asarray(index.tile_ptr)
    h_tmb = np.asarray(index.tile_max_b)
    h_tml = np.asarray(index.tile_max_l)
    if kind == "fp32":
        h_docids = np.asarray(index.docids)
        h_wb = np.asarray(index.w_b)
        h_wl = np.asarray(index.w_l)
    elif kind == "q8":
        h_packed = np.asarray(index.packed)
        h_qb = np.asarray(index.qb)
        h_ql = np.asarray(index.ql)
        h_pptr = np.asarray(index.pack_ptr)
        h_grids = {n: np.asarray(getattr(index, n)) for n in
                   ("width", "first", "scale_b", "zero_b",
                    "scale_l", "zero_l")}
    else:
        raise ValueError(f"unknown gather kind: {kind!r}")

    shard_gather = []   # per-shard gather tuples (numpy)
    tmb_l, tml_l, base_l = [], [], []
    nnz = np.zeros(n_shards, dtype=np.int64)
    for s in range(n_shards):
        t0 = min(s * tps, n_tiles)
        t1 = min((s + 1) * tps, n_tiles)
        flat, lp_real, _ = _csr_shard_gather(h_ptr, t0, t1)
        lp = _pad_ptr(lp_real, tps)
        nnz[s] = len(flat)
        if kind == "fp32":
            local_doc = (h_docids[flat].astype(np.int64)
                         - t0 * tile_size).astype(np.int32)
            shard_gather.append((local_doc, h_wb[flat], h_wl[flat], lp))
        else:
            wflat, lpw_real, _ = _csr_shard_gather(h_pptr, t0, t1)
            lpw = _pad_ptr(lpw_real, tps)
            shard_gather.append((
                h_packed[wflat], h_qb[flat], h_ql[flat], lp, lpw,
                *(_pad_cols(g[:, t0:t1], tps) for g in
                  (h_grids["width"], h_grids["first"], h_grids["scale_b"],
                   h_grids["zero_b"], h_grids["scale_l"],
                   h_grids["zero_l"]))))
        tmb = np.zeros((n_terms, tps), dtype=np.float32)
        tml = np.zeros((n_terms, tps), dtype=np.float32)
        tmb[:, :t1 - t0] = h_tmb[:, t0:t1]
        tml[:, :t1 - t0] = h_tml[:, t0:t1]
        tmb_l.append(tmb)
        tml_l.append(tml)
        base_l.append(t0 * tile_size)

    # pad every shard's flat leaves (slots 0-2: postings, and words for
    # q8) to one length (fp32: the longest shard's sentinel tail), then
    # stack each gather slot on the shard axis
    if kind == "fp32":
        shard_gather = [(*with_sentinel_tail(*sg[:3], pad_len=index.pad_len,
                                             nnz=int(nnz.max())), sg[3])
                        for sg in shard_gather]
    gather = []
    for i in range(len(shard_gather[0])):
        leaves = [sg[i] for sg in shard_gather]
        if i < 3:
            m = max(1, max(len(a) for a in leaves))
            leaves = [_pad_flat(a, m) for a in leaves]
        gather.append(jnp.asarray(np.stack(leaves)))

    n_real = np.clip(n_tiles - tps * np.arange(n_shards), 0, tps
                     ).astype(np.int32)

    return ShardedImpactIndex(
        n_shards=n_shards, n_docs=index.n_docs, n_terms=n_terms,
        tile_size=tile_size, n_tiles=n_tiles, tiles_per_shard=tps,
        pad_len=index.pad_len,
        doc_base=jnp.asarray(np.array(base_l, dtype=np.int32)),
        n_real_tiles=jnp.asarray(n_real), nnz_per_shard=nnz,
        gather=tuple(gather), gather_kind=kind,
        tile_max_b=jnp.asarray(np.stack(tmb_l)),
        tile_max_l=jnp.asarray(np.stack(tml_l)),
        sigma_b=index.sigma_b, sigma_l=index.sigma_l,
        orig_of_new=index.orig_of_new)


def place_on_mesh(sharded: ShardedImpactIndex, mesh,
                  axis_name: str = "shard") -> ShardedImpactIndex:
    """Lay a stacked shard index out over a one-axis mesh: shard ``s`` of
    every stacked leaf lives on device ``s`` and the global per-term
    maxima are replicated. Done once when the index is opened, so no
    search call moves index bytes between devices."""
    if mesh.shape[axis_name] != sharded.n_shards:
        raise ValueError(
            f"mesh axis {axis_name!r} has size {mesh.shape[axis_name]} but "
            f"the index has {sharded.n_shards} shards")

    def stacked(a):
        spec = P(axis_name, *([None] * (a.ndim - 1)))
        return jax.device_put(a, NamedSharding(mesh, spec))

    def replicated(a):
        return jax.device_put(a, NamedSharding(mesh, P()))

    return dataclasses.replace(
        sharded, gather=tuple(stacked(a) for a in sharded.gather),
        doc_base=stacked(sharded.doc_base),
        n_real_tiles=stacked(sharded.n_real_tiles),
        tile_max_b=stacked(sharded.tile_max_b),
        tile_max_l=stacked(sharded.tile_max_l),
        sigma_b=replicated(sharded.sigma_b),
        sigma_l=replicated(sharded.sigma_l))
