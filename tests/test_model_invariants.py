"""Model-level invariants beyond shape checks."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.transformer import (TransformerConfig, decode_step,
                                      init_params, logits_fn, forward,
                                      prefill)


def test_lm_greedy_decode_loop_consistency():
    """Greedy decode token-by-token == argmax of the full forward pass."""
    cfg = TransformerConfig(n_layers=2, d_model=64, n_heads=4, n_kv_heads=2,
                            d_ff=128, vocab=131, compute_dtype=jnp.float32,
                            remat=False)
    params = init_params(cfg, jax.random.PRNGKey(5))
    toks = jax.random.randint(jax.random.PRNGKey(6), (1, 8), 0, cfg.vocab)
    # reference: teacher-forced argmax continuation
    ctx = toks
    for _ in range(4):
        h, _, _ = forward(cfg, params, ctx)
        nxt = logits_fn(cfg, params, h)[:, -1].argmax(-1)[:, None]
        ctx = jnp.concatenate([ctx, nxt], axis=1)
    # decode loop with KV cache
    lg, cache = prefill(cfg, params, toks, max_len=16)
    cur = lg[:, -1].argmax(-1)[:, None]
    got = [int(cur[0, 0])]
    pos = 8
    for _ in range(3):
        lg, cache = decode_step(cfg, params, cur, cache, jnp.int32(pos))
        cur = lg[:, -1].argmax(-1)[:, None]
        got.append(int(cur[0, 0]))
        pos += 1
    expect = [int(t) for t in np.asarray(ctx[0, 8:])]
    assert got == expect, (got, expect)


def test_moe_group_count_invariance_no_drop():
    """With no-drop capacity, MoE output is identical for 1 vs 4 dispatch
    groups (group-wise capacity only changes *drop* behaviour)."""
    from repro.models.transformer import MoEConfig, Rules, lm_loss
    cfg = TransformerConfig(n_layers=1, d_model=32, n_heads=2, n_kv_heads=2,
                            d_ff=0, vocab=64,
                            moe=MoEConfig(4, 2, 16, capacity_factor=16.0),
                            compute_dtype=jnp.float32, remat=False)
    params = init_params(cfg, jax.random.PRNGKey(7))
    toks = jax.random.randint(jax.random.PRNGKey(8), (4, 8), 0, 64)
    batch = {"tokens": toks, "targets": jnp.roll(toks, -1, 1)}
    l1 = lm_loss(cfg, params, batch, Rules(dp_size=1))
    l4 = lm_loss(cfg, params, batch, Rules(dp_size=4))
    np.testing.assert_allclose(float(l1), float(l4), rtol=1e-5)
