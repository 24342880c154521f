"""Host-level collectives built on ``shard_map`` + ``lax.ppermute``.

Semantics: the input's leading dim is the *device contribution* axis — it is
sharded over the named mesh axis, each device's slice is its local value,
and the reduction returns the elementwise sum of all slices, replicated.
On a 1-device mesh every collective is the identity (sum of one slice),
which is what the single-device tests pin down; on an n-device mesh
``ring_all_reduce(stack(x_i)) == sum_i x_i`` exactly matches ``lax.psum``
of per-device values (the subprocess test checks this against psum).

The ring is the classic 2(n-1)-step algorithm — an (n-1)-step chunked
reduce-scatter followed by an (n-1)-step all-gather — so each device moves
2(n-1)/n of the payload regardless of n, instead of the (n-1)x payload a
naive gather-everything would move. ``hierarchical_all_reduce`` composes two
rings, intra-group then inter-group, matching the pod/ICI topology of the
production meshes (ring within a pod, ring across pods on the slower DCN
axis moves 1/n_inner of the bytes).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def _ring_sum(x, axis_name: str, n: int):
    """In-shard_map ring all-reduce of each device's ``x`` over one axis."""
    if n == 1:
        return x
    shape, size = x.shape, x.size
    flat = x.reshape(-1)
    pad = (-size) % n
    if pad:
        flat = jnp.pad(flat, (0, pad))
    chunks = flat.reshape(n, -1)
    idx = jax.lax.axis_index(axis_name)
    fwd = [(i, (i + 1) % n) for i in range(n)]

    def rs_step(s, ch):
        # step s: send partial chunk (idx - s), receive (idx - s - 1), add
        blk = jnp.take(ch, (idx - s) % n, axis=0)
        blk = jax.lax.ppermute(blk, axis_name, fwd)
        return ch.at[(idx - s - 1) % n].add(blk)

    chunks = jax.lax.fori_loop(0, n - 1, rs_step, chunks)
    # device idx now owns the fully-reduced chunk (idx + 1) % n

    def ag_step(s, ch):
        blk = jnp.take(ch, (idx + 1 - s) % n, axis=0)
        blk = jax.lax.ppermute(blk, axis_name, fwd)
        return ch.at[(idx - s) % n].set(blk)

    chunks = jax.lax.fori_loop(0, n - 1, ag_step, chunks)
    return chunks.reshape(-1)[:size].reshape(shape)


def _shard_spec(ndim: int, axes) -> P:
    return P(axes, *([None] * (ndim - 1)))


def ring_all_reduce(x, mesh, axis_name: str):
    """Sum the per-device slices of ``x`` along dim 0, replicated.

    ``x.shape[0]`` must divide by ``mesh.shape[axis_name]``; the result has
    leading dim ``x.shape[0] // n`` (one contribution per device). On a
    1-device mesh this is the identity.
    """
    n = mesh.shape[axis_name]
    f = jax.shard_map(partial(_ring_sum, axis_name=axis_name, n=n),
                      mesh=mesh, in_specs=_shard_spec(x.ndim, axis_name),
                      out_specs=P(*([None] * x.ndim)), check_vma=False)
    return f(x)


def hierarchical_all_reduce(x, mesh, inner_axis: str, outer_axis: str):
    """Two-phase all-reduce: ring within ``inner_axis`` groups, then ring
    across ``outer_axis`` — the intra-pod / inter-pod split. Contributions
    are the ``x`` slices along dim 0 (one per device, inner-major)."""
    n_in, n_out = mesh.shape[inner_axis], mesh.shape[outer_axis]

    def f(local):
        y = _ring_sum(local, inner_axis, n_in)
        return _ring_sum(y, outer_axis, n_out)

    return jax.shard_map(f, mesh=mesh,
                         in_specs=_shard_spec(x.ndim, (outer_axis, inner_axis)),
                         out_specs=P(*([None] * x.ndim)), check_vma=False)(x)


def reduce_scatter(x, mesh, axis_name: str):
    """Ring reduce-scatter: device i ends with chunk i of the summed
    contributions. Returns the globally-sharded sum (shape of one
    contribution, leading dim sharded over ``axis_name``). The
    contribution row count ``x.shape[0] // n`` must itself divide by
    ``n`` so the scattered chunks partition it exactly."""
    n = mesh.shape[axis_name]
    rows = x.shape[0] // n
    if n > 1 and rows % n:
        raise ValueError(
            f"reduce_scatter needs contribution rows ({rows}) divisible "
            f"by mesh axis {axis_name!r} ({n}) to scatter without overlap")

    def f(local):
        y = _ring_sum(local, axis_name, n)  # full sum of one contribution
        i = jax.lax.axis_index(axis_name)
        chunk = local.shape[0] // n
        return jax.lax.dynamic_slice_in_dim(y, i * chunk, chunk, axis=0)

    return jax.shard_map(f, mesh=mesh,
                         in_specs=_shard_spec(x.ndim, axis_name),
                         out_specs=_shard_spec(x.ndim, axis_name),
                         check_vma=False)(x)


def ring_gather_stack(local, axis_name: str, n: int):
    """In-shard_map building block: ring all-gather every device's ``local``
    into a new leading axis ordered by device index ([*] -> [n, *], entry j
    = device j's contribution). This is the primitive behind both
    ``ring_all_gather`` and the sharded-retrieval top-k queue merge
    (``serve.sharded``), which needs the stacked form to keep the
    shard-order stable-tie semantics of the single-device queue."""
    if n == 1:
        return local[None]
    idx = jax.lax.axis_index(axis_name)
    fwd = [(i, (i + 1) % n) for i in range(n)]
    out = jnp.zeros((n,) + local.shape, local.dtype).at[idx].set(local)

    def step(s, carry):
        blk, acc = carry
        blk = jax.lax.ppermute(blk, axis_name, fwd)
        return blk, acc.at[(idx - s - 1) % n].set(blk)

    _, out = jax.lax.fori_loop(0, n - 1, step, (local, out))
    return out


def ring_all_gather(x, mesh, axis_name: str):
    """All-gather the per-device slices: every device ends with the full
    concatenation (result replicated, same global shape as ``x``)."""
    n = mesh.shape[axis_name]

    def f(local):
        out = ring_gather_stack(local, axis_name, n)
        return out.reshape((n * local.shape[0],) + local.shape[1:])

    return jax.shard_map(f, mesh=mesh,
                         in_specs=_shard_spec(x.ndim, axis_name),
                         out_specs=P(*([None] * x.ndim)), check_vma=False)(x)
