"""Pure-jnp oracles for every Pallas kernel (the allclose targets)."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def guided_score_tile_ref(offs, wb, wl, essential, prefix_beta, th_lo,
                          alpha, beta, gamma, *, tile_size: int):
    """Oracle for kernels.guided_score.guided_score_tile -> [6, tile_size]."""
    nq, p = offs.shape
    S = tile_size
    valid = offs >= 0
    offs_safe = jnp.where(valid, offs, S).astype(jnp.int32)
    seg = (jnp.arange(nq, dtype=jnp.int32)[:, None] * (S + 1) + offs_safe
           ).ravel()
    dense_b = jax.ops.segment_sum(
        (wb * valid).ravel(), seg, num_segments=nq * (S + 1)
    ).reshape(nq, S + 1)[:, :S]
    dense_l = jax.ops.segment_sum(
        (wl * valid).ravel(), seg, num_segments=nq * (S + 1)
    ).reshape(nq, S + 1)[:, :S]
    cnt = jax.ops.segment_sum(
        valid.ravel().astype(jnp.float32), seg, num_segments=nq * (S + 1)
    ).reshape(nq, S + 1)[:, :S]
    ess = essential.astype(jnp.float32)
    survive = (jnp.einsum("t,ts->s", ess, cnt) > 0)

    def body(j, carry):
        i = nq - 1 - j
        sb, sl, alive = carry
        l_part = beta * sb + (1 - beta) * sl
        ok = (ess[i] > 0) | (l_part + prefix_beta[i] > th_lo)
        alive = alive & ok
        gate = (survive & alive).astype(jnp.float32)
        return sb + gate * dense_b[i], sl + gate * dense_l[i], alive

    zero = jnp.zeros(S, jnp.float32)
    sb, sl, alive = jax.lax.fori_loop(0, nq, body,
                                      (zero, zero, jnp.ones(S, bool)))
    return jnp.stack([
        alpha * sb + (1 - alpha) * sl,
        beta * sb + (1 - beta) * sl,
        gamma * sb + (1 - gamma) * sl,
        (survive & alive).astype(jnp.float32),
        survive.astype(jnp.float32),
        cnt.sum(0),
    ])


def flash_attention_ref(q, k, v, *, causal: bool = True,
                        sm_scale: float | None = None, kv_offset: int = 0):
    """Oracle for kernels.flash_attention (GQA + causal + offset)."""
    h, sq, d = q.shape
    hkv, skv, _ = k.shape
    group = h // hkv
    if sm_scale is None:
        sm_scale = 1.0 / (d ** 0.5)
    kg = jnp.repeat(k, group, axis=0)
    vg = jnp.repeat(v, group, axis=0)
    s = jnp.einsum("hqd,hkd->hqk", q.astype(jnp.float32),
                   kg.astype(jnp.float32)) * sm_scale
    if causal:
        q_pos = kv_offset + jnp.arange(sq)[:, None]
        k_pos = jnp.arange(skv)[None, :]
        s = jnp.where(q_pos >= k_pos, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("hqk,hkd->hqd", p,
                      vg.astype(jnp.float32)).astype(q.dtype)


def embedding_bag_ref(table, indices, weights):
    """Oracle for kernels.embedding_bag: weighted bag sum via take."""
    rows = jnp.take(table, indices, axis=0)        # [B, L, D]
    return (rows * weights[..., None]).sum(axis=1).astype(table.dtype)
