"""The fp32 tile gather: one window per (term, tile) run, bit-identical to
the per-element gather it replaced.

``gather_tile`` fetches each run as the aligned 128-lane rows that hold it
and shifts it to lane 0. The reference below is the old formula
(``pad_len`` clipped scalar ``jnp.take``s per run) applied to the real
postings alone, with no sentinel tail. Every query term and every tile of
a small index are compared, so the last runs of the flat arrays, whose
windows reach into the tail and would be clamped without it, are covered;
so are the shard-local arrays that ``shard_index`` pads.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import build_index
from repro.core.index import INVALID_DOC, flat_len, gather_tile
from repro.core.shard_plan import shard_index


def _scalar_gather(docids, w_b, w_l, tile_ptr, q_terms, tile, qw_b, qw_l,
                   *, pad_len, tile_size):
    """The per-element gather: ``pad_len`` clipped scalar fetches per run."""
    start = tile_ptr[q_terms, tile]
    cnt = tile_ptr[q_terms, tile + 1] - start
    idx = start[:, None] + jnp.arange(pad_len, dtype=jnp.int32)[None, :]
    mask = jnp.arange(pad_len, dtype=jnp.int32)[None, :] < cnt[:, None]
    idx = jnp.where(mask, idx, 0)
    d = jnp.take(docids, idx, mode="clip")
    offs = jnp.where(mask, d - tile * tile_size, -1).astype(jnp.int32)
    wb = jnp.where(mask, jnp.take(w_b, idx, mode="clip"), 0.0)
    wl = jnp.where(mask, jnp.take(w_l, idx, mode="clip"), 0.0)
    return offs, wb * qw_b[:, None], wl * qw_l[:, None]


@pytest.fixture(scope="module", params=[32, 256], ids=["narrow", "wide"])
def index(request, small_corpus):
    """pad_len equals tile_size on this corpus: a window inside one or two
    128-lane rows, and one across three."""
    index = build_index(small_corpus.merged("scaled"),
                        tile_size=request.param)
    assert index.pad_len == request.param
    return index


def _assert_gathers_match(flat, nnz, tile_ptr, n_tiles, *, pad_len,
                          tile_size):
    """``gather_tile`` on ``flat`` (with its tail) == the scalar gather on
    the first ``nnz`` entries, for every term and every tile."""
    n_terms = tile_ptr.shape[0]
    q_terms = jnp.arange(n_terms, dtype=jnp.int32)
    rng = np.random.default_rng(0)
    qw_b = jnp.asarray(rng.uniform(0.5, 2.0, n_terms), jnp.float32)
    qw_l = jnp.asarray(rng.uniform(0.5, 2.0, n_terms), jnp.float32)
    real = tuple(a[:nnz] for a in flat)
    tiles = jnp.arange(n_tiles, dtype=jnp.int32)
    kw = dict(pad_len=pad_len, tile_size=tile_size)
    got = jax.vmap(lambda t: gather_tile(*flat, tile_ptr, q_terms, t, qw_b,
                                         qw_l, **kw))(tiles)
    want = jax.vmap(lambda t: _scalar_gather(*real, tile_ptr, q_terms, t,
                                             qw_b, qw_l, **kw))(tiles)
    for name, g, w in zip(("offs", "wb", "wl"), got, want):
        assert g.dtype == w.dtype and g.shape == w.shape, name
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w),
                                      err_msg=name)
    # the comparison reached runs whose window ends past the real postings
    starts = np.asarray(tile_ptr[:, :n_tiles])
    cnt = np.diff(np.asarray(tile_ptr[:, :n_tiles + 1]), axis=1)
    assert np.any((cnt > 0) & (starts + pad_len > nnz))


def test_index_keeps_its_real_posting_count_and_a_sentinel_tail(
        small_corpus, index):
    merged = small_corpus.merged("scaled")
    assert index.nnz == merged.nnz
    for a, fill in ((index.docids, INVALID_DOC), (index.w_b, 0.0),
                    (index.w_l, 0.0)):
        a = np.asarray(a)
        assert a.shape == (flat_len(index.nnz, index.pad_len),)
        assert a.shape[0] >= index.nnz + index.pad_len + 127
        assert np.all(a[index.nnz:] == fill)
    np.testing.assert_array_equal(np.asarray(index.tile_ptr)[:, -1],
                                  merged.indptr[1:])


def test_gather_tile_matches_per_slot_gather(index):
    _assert_gathers_match((index.docids, index.w_b, index.w_l), index.nnz,
                          index.tile_ptr, index.n_tiles,
                          pad_len=index.pad_len, tile_size=index.tile_size)


@pytest.mark.parametrize("n_shards", [2, 3])
def test_gather_tile_matches_per_slot_gather_on_shards(index, n_shards):
    sh = shard_index(index, n_shards)
    for s in range(n_shards):
        nnz = int(sh.nnz_per_shard[s])
        flat = (sh.docids[s], sh.w_b[s], sh.w_l[s])
        assert all(a.shape[0] >= nnz + sh.pad_len for a in flat)
        _assert_gathers_match(flat, nnz, sh.tile_ptr[s], sh.tiles_per_shard,
                              pad_len=sh.pad_len, tile_size=sh.tile_size)


def test_gather_tile_refuses_flat_arrays_without_the_tail(index):
    real = tuple(a[:index.nnz] for a in (index.docids, index.w_b, index.w_l))
    with pytest.raises(ValueError, match="sentinel tail"):
        gather_tile(*real, index.tile_ptr, jnp.arange(4, dtype=jnp.int32),
                    jnp.int32(0), pad_len=index.pad_len,
                    tile_size=index.tile_size)
