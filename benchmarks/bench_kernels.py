"""Kernel microbenchmarks: Pallas (interpret mode on CPU) vs jnp oracle.

Interpret-mode timings measure Python emulation, not TPU performance — the
derived column carries the correctness deltas and shapes; wall numbers are
for regression tracking only."""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ref
from repro.kernels.guided_score import guided_score_tile

from .common import emit


def _time(fn, *args, reps=3):
    fn(*args)  # compile
    t0 = time.perf_counter()
    for _ in range(reps):
        jax.block_until_ready(fn(*args))
    return (time.perf_counter() - t0) / reps * 1e3


def run(out) -> None:
    rng = np.random.default_rng(0)
    # guided_score
    nq, p, s = 16, 256, 1024
    offs = np.sort(rng.choice(s, (nq, p), replace=True), axis=1)
    offs = jnp.asarray(offs, jnp.int32)
    wb = jnp.asarray(rng.random((nq, p)), jnp.float32)
    wl = jnp.asarray(rng.random((nq, p)), jnp.float32)
    ess = jnp.asarray(rng.random(nq) < 0.5, jnp.float32)
    pb = jnp.asarray(np.cumsum(rng.random(nq)), jnp.float32)
    args = (offs, wb, wl, ess, pb, jnp.float32(2.0),
            jnp.float32(1.0), jnp.float32(0.3), jnp.float32(0.05))
    t_k = _time(lambda *a: guided_score_tile(*a, tile_size=s, block_s=512),
                *args)
    t_r = _time(lambda *a: ref.guided_score_tile_ref(*a, tile_size=s), *args)
    err = float(jnp.max(jnp.abs(
        guided_score_tile(*args, tile_size=s, block_s=512)
        - ref.guided_score_tile_ref(*args, tile_size=s))))
    out(emit("kernels/guided_score/nq16_p256_s1024", t_k,
             {"ref_ms": t_r, "max_err": err}))
