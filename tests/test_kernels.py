"""Per-kernel interpret-mode validation: shape/dtype sweeps vs ref.py."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.embedding_bag import embedding_bag
from repro.kernels.flash_attention import flash_attention
from repro.kernels.guided_score import guided_score_chunk, guided_score_tile


def _tile_inputs(rng, nq, p, tile_size, density=0.5):
    """Sorted distinct offsets with -1 padding; row 0's run fills pad_len."""
    n_valid = int(p * density)
    offs = np.full((nq, p), -1, np.int32)
    for i in range(nq):
        n = p if i == 0 else n_valid
        offs[i, :n] = np.sort(rng.choice(tile_size, size=n, replace=False))
    wb = (rng.random((nq, p)) * 3).astype(np.float32) * (offs >= 0)
    wl = (rng.random((nq, p)) * 5).astype(np.float32) * (offs >= 0)
    return jnp.asarray(offs), jnp.asarray(wb), jnp.asarray(wl)


@pytest.mark.parametrize("nq,p,tile_size,block_s", [
    (4, 64, 256, 128), (8, 128, 512, 512), (16, 128, 1024, 256),
    (5, 96, 384, 128),  # non-power-of-two nq/p
])
def test_guided_score_matches_ref(nq, p, tile_size, block_s):
    rng = np.random.default_rng(nq * 1000 + p)
    offs, wb, wl = _tile_inputs(rng, nq, p, tile_size)
    essential = jnp.asarray(rng.random(nq) < 0.5, jnp.float32)
    prefix_beta = jnp.asarray(np.cumsum(rng.random(nq)), jnp.float32)
    args = (offs, wb, wl, essential, prefix_beta, jnp.float32(2.0),
            jnp.float32(1.0), jnp.float32(0.3), jnp.float32(0.05))
    out_k = guided_score_tile(*args, tile_size=tile_size, block_s=block_s)
    out_r = ref.guided_score_tile_ref(*args, tile_size=tile_size)
    assert out_k.shape == (6, tile_size)
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r),
                               rtol=1e-5, atol=1e-5)
    _assert_slot_counts(out_k[5], out_r[5], offs, tile_size)


def _assert_slot_counts(row5, row5_ref, offs, tile_size):
    """Row 5 is the per-slot posting count, exactly: equal to the oracle
    and to a count of the valid offsets."""
    offs = np.asarray(offs)
    want = np.bincount(offs[offs >= 0], minlength=tile_size)
    np.testing.assert_array_equal(np.asarray(row5), np.asarray(row5_ref))
    np.testing.assert_array_equal(np.asarray(row5), want)


@pytest.mark.parametrize("alpha,beta,gamma,th_lo", [
    (0.0, 0.0, 0.0, -np.inf), (1.0, 1.0, 0.05, 0.5), (0.7, 0.2, 0.0, 5.0)])
def test_guided_score_param_sweep(alpha, beta, gamma, th_lo):
    rng = np.random.default_rng(0)
    offs, wb, wl = _tile_inputs(rng, 8, 64, 256)
    essential = jnp.asarray(rng.random(8) < 0.6, jnp.float32)
    prefix_beta = jnp.asarray(np.cumsum(rng.random(8)), jnp.float32)
    args = (offs, wb, wl, essential, prefix_beta, jnp.float32(th_lo),
            jnp.float32(alpha), jnp.float32(beta), jnp.float32(gamma))
    out_k = guided_score_tile(*args, tile_size=256, block_s=128)
    out_r = ref.guided_score_tile_ref(*args, tile_size=256)
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r),
                               rtol=1e-5, atol=1e-5)


def test_guided_score_matches_traversal_scorer(small_corpus):
    """Kernel == the engine's jnp score_tile on real index data."""
    from repro.core import build_index
    from repro.core.traversal import _gather_tile, _combine
    corpus = small_corpus
    index = build_index(corpus.merged("scaled"), tile_size=256)
    qt = jnp.asarray(corpus.queries[0])
    qwb = jnp.asarray(corpus.q_weights_b[0])
    qwl = jnp.asarray(corpus.q_weights_l[0])
    offs, wb, wl = _gather_tile(index.docids, index.w_b, index.w_l,
                                index.tile_ptr, qt, qwb, qwl, jnp.int32(2),
                                pad_len=index.pad_len,
                                tile_size=index.tile_size)
    sig_b = qwb * index.sigma_b[qt]
    sig_l = qwl * index.sigma_l[qt]
    alpha, beta = 1.0, 0.3
    m_alpha = _combine(alpha, sig_b, sig_l)
    m_beta = _combine(beta, sig_b, sig_l)
    essential = (jnp.cumsum(m_alpha) > 1.0).astype(jnp.float32)
    prefix_beta = jnp.cumsum(m_beta)
    # pad P to a lane multiple for the kernel
    padp = (-index.pad_len) % 128
    pad = lambda a, fill: jnp.pad(a, ((0, 0), (0, padp)),
                                  constant_values=fill)
    out_k = guided_score_tile(pad(offs, -1), pad(wb, 0), pad(wl, 0),
                              essential, prefix_beta, jnp.float32(2.0),
                              jnp.float32(alpha), jnp.float32(beta),
                              jnp.float32(0.05), tile_size=256, block_s=256)
    out_r = ref.guided_score_tile_ref(offs, wb, wl, essential, prefix_beta,
                                      jnp.float32(2.0),
                                      jnp.float32(alpha), jnp.float32(beta),
                                      jnp.float32(0.05), tile_size=256)
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("n_chunk,nq,p,tile_size,block_s", [
    (4, 8, 64, 256, 128), (3, 5, 96, 384, 128), (2, 8, 128, 512, 512)])
def test_guided_score_chunk_matches_per_tile(n_chunk, nq, p, tile_size,
                                             block_s):
    """The multi-tile chunk kernel must equal per-tile guided_score_tile
    calls on every live tile and publish all-zero planes for skipped ones
    (the SMEM skip predicate gating the scatter/freeze passes)."""
    rng = np.random.default_rng(n_chunk * 100 + nq)
    tiles = [_tile_inputs(rng, nq, p, tile_size) for _ in range(n_chunk)]
    offs = jnp.stack([t[0] for t in tiles])
    wb = jnp.stack([t[1] for t in tiles])
    wl = jnp.stack([t[2] for t in tiles])
    essential = jnp.asarray(rng.random((n_chunk, nq)) < 0.5, jnp.float32)
    prefix_beta = jnp.asarray(np.cumsum(rng.random((n_chunk, nq)), axis=1),
                              jnp.float32)
    skip = jnp.asarray([i % 2 for i in range(n_chunk)], jnp.int32)
    scal = (jnp.float32(2.0), jnp.float32(1.0), jnp.float32(0.3),
            jnp.float32(0.05))
    out = guided_score_chunk(offs, wb, wl, essential, prefix_beta, skip,
                             *scal, tile_size=tile_size, block_s=block_s)
    assert out.shape == (n_chunk, 6, tile_size)
    for c in range(n_chunk):
        if int(skip[c]):
            np.testing.assert_array_equal(np.asarray(out[c]), 0.0)
        else:
            per_tile = guided_score_tile(
                offs[c], wb[c], wl[c], essential[c], prefix_beta[c],
                *scal, tile_size=tile_size, block_s=block_s)
            np.testing.assert_allclose(np.asarray(out[c]),
                                       np.asarray(per_tile),
                                       rtol=1e-5, atol=1e-5)
            want = ref.guided_score_tile_ref(
                offs[c], wb[c], wl[c], essential[c], prefix_beta[c],
                *scal, tile_size=tile_size)
            _assert_slot_counts(out[c, 5], want[5], offs[c], tile_size)


def test_guided_score_chunk_all_skipped_is_zero():
    rng = np.random.default_rng(0)
    offs, wb, wl = _tile_inputs(rng, 4, 32, 128)
    offs, wb, wl = (jnp.stack([a, a]) for a in (offs, wb, wl))
    essential = jnp.ones((2, 4), jnp.float32)
    prefix_beta = jnp.ones((2, 4), jnp.float32)
    out = guided_score_chunk(offs, wb, wl, essential, prefix_beta,
                             jnp.ones(2, jnp.int32), jnp.float32(0.0),
                             jnp.float32(1.0), jnp.float32(0.3),
                             jnp.float32(0.05), tile_size=128)
    np.testing.assert_array_equal(np.asarray(out), 0.0)


def test_guided_score_refuses_runs_wider_than_max_pad_len():
    """A tile wider than the kernels fit in VMEM is refused by name, not
    left to a compiler out-of-memory error on the chip."""
    from repro.kernels.guided_score import MAX_PAD_LEN
    p = 2 * MAX_PAD_LEN
    rows = jax.ShapeDtypeStruct((1, 2, p), jnp.float32)
    per_tile = jax.ShapeDtypeStruct((1, 2), jnp.float32)
    s = jnp.float32(0.0)
    with pytest.raises(ValueError, match="pad_len <= 4096"):
        jax.eval_shape(
            lambda o, b, l, e, pb: guided_score_chunk(
                o, b, l, e, pb, jnp.zeros(1, jnp.int32), s, s, s, s,
                tile_size=p),
            jax.ShapeDtypeStruct((1, 2, p), jnp.int32), rows, rows,
            per_tile, per_tile)


@pytest.mark.parametrize("h,hkv,sq,skv,d,causal,off", [
    (4, 4, 128, 128, 64, True, 0),
    (8, 2, 128, 256, 64, True, 128),   # GQA + decode-style offset
    (4, 1, 64, 128, 128, False, 0),    # MQA, bidirectional
    (2, 2, 256, 256, 32, True, 0),
])
def test_flash_attention_matches_ref(h, hkv, sq, skv, d, causal, off):
    rng = np.random.default_rng(h * 100 + skv)
    q = jnp.asarray(rng.standard_normal((h, sq, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((hkv, skv, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((hkv, skv, d)), jnp.float32)
    out_k = flash_attention(q, k, v, causal=causal, kv_offset=off,
                            block_q=64, block_k=64)
    out_r = ref.flash_attention_ref(q, k, v, causal=causal, kv_offset=off)
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_dtypes(dtype):
    rng = np.random.default_rng(1)
    q = jnp.asarray(rng.standard_normal((2, 128, 64)), dtype)
    k = jnp.asarray(rng.standard_normal((2, 128, 64)), dtype)
    v = jnp.asarray(rng.standard_normal((2, 128, 64)), dtype)
    out_k = flash_attention(q, k, v, causal=True)
    out_r = ref.flash_attention_ref(q, k, v, causal=True)
    tol = 2e-4 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out_k, np.float32),
                               np.asarray(out_r, np.float32),
                               rtol=tol, atol=tol)


def test_flash_attention_batched_vmap():
    rng = np.random.default_rng(2)
    q = jnp.asarray(rng.standard_normal((3, 4, 128, 64)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((3, 2, 128, 64)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((3, 2, 128, 64)), jnp.float32)
    f = lambda q, k, v: flash_attention(q, k, v, causal=True)
    out_k = jax.vmap(f)(q, k, v)
    out_r = jax.vmap(lambda q, k, v: ref.flash_attention_ref(
        q, k, v, causal=True))(q, k, v)
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("v,d,b,l", [
    (64, 32, 16, 4), (256, 128, 32, 8), (1000, 64, 8, 12)])
def test_embedding_bag_matches_ref(v, d, b, l):
    rng = np.random.default_rng(v + b)
    table = jnp.asarray(rng.standard_normal((v, d)), jnp.float32)
    idx = jnp.asarray(rng.integers(0, v, (b, l)), jnp.int32)
    w = jnp.asarray(rng.random((b, l)), jnp.float32)
    out_k = embedding_bag(table, idx, w, block_b=min(8, b))
    out_r = ref.embedding_bag_ref(table, idx, w)
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r),
                               rtol=1e-5, atol=1e-5)


def test_embedding_bag_padding_weights():
    table = jnp.asarray(np.eye(8, 4), jnp.float32)
    idx = jnp.asarray([[1, 2, 0], [3, 0, 0]], jnp.int32)
    w = jnp.asarray([[1.0, 1.0, 0.0], [2.0, 0.0, 0.0]], jnp.float32)
    out = embedding_bag(table, idx, w, block_b=2)
    expect = np.zeros((2, 4), np.float32)
    expect[0, 1] = 1.0
    expect[0, 2] = 1.0
    expect[1, 3] = 2.0
    np.testing.assert_allclose(np.asarray(out), expect)
