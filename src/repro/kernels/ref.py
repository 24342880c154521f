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
