"""TPU v5e compiles of the guided_score kernels at the chip smoke's widths.

Interpret mode (every other kernel test) cannot see what the TPU compiler
refuses: Mosaic relayouts, block tiling rules, scoped VMEM. These tests lower
each kernel for a described, unattached v5e and assert that the compiled
program holds the native kernel (``tpu_custom_call``). The chunk kernels are
also compiled under ``vmap``, the way the batched traversal calls them.

The topology is described inside a module fixture, never at import: only one
process may load the TPU library at a time, so every xdist worker must
collect the same tests and only the worker that runs this file loads it.
"""
import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.index.compressed import raw_words_len
from repro.kernels.guided_score import (MAX_PAD_LEN, guided_score_chunk,
                                        guided_score_chunk_q,
                                        guided_score_tile,
                                        guided_score_tile_q)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without the chip: keep it out entirely."""
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


def _args(name, cfg, sds, batch=()):
    """Abstract inputs of one kernel at ``cfg``'s widths (pad_len reaches
    tile_size: the head terms of a 2^20-doc corpus fill tiles)."""
    f32, i32 = jnp.float32, jnp.int32
    nq, p, c = cfg.query_terms, cfg.tile_size, cfg.chunk_tiles
    wp = raw_words_len(p)
    b = tuple(batch)
    s = lambda *shape: b + shape
    scal = tuple(sds(s(), f32) for _ in range(4))
    if name == "tile":
        return (sds(s(nq, p), i32), sds(s(nq, p), f32), sds(s(nq, p), f32),
                sds(s(nq), f32), sds(s(nq), f32), *scal)
    if name == "chunk":
        return (sds(s(c, nq, p), i32), sds(s(c, nq, p), f32),
                sds(s(c, nq, p), f32), sds(s(c, nq), f32),
                sds(s(c, nq), f32), sds(s(c), i32), *scal)
    if name == "tile_q":
        return (sds(s(nq, wp), i32), sds(s(nq, p), f32), sds(s(nq, p), f32),
                sds(s(3, nq), i32), sds(s(4, nq), f32), sds(s(nq), f32),
                sds(s(nq), f32), sds(s(nq), f32), sds(s(nq), f32), *scal)
    return (sds(s(c, nq, wp), i32), sds(s(c, nq, p), f32),
            sds(s(c, nq, p), f32), sds(s(c, 3, nq), i32),
            sds(s(c, 4, nq), f32), sds(s(nq), f32), sds(s(nq), f32),
            sds(s(c, nq), f32), sds(s(c, nq), f32), sds(s(c), i32), *scal)


_KERNELS = {"tile": guided_score_tile, "chunk": guided_score_chunk,
            "tile_q": guided_score_tile_q, "chunk_q": guided_score_chunk_q}


@pytest.mark.parametrize("batched", [False, True], ids=["single", "vmap"])
@pytest.mark.parametrize("name", list(_KERNELS))
def test_guided_score_compiles_for_v5e(one_chip, no_persistent_cache,
                                       chip_smoke, name, batched):
    cfg = chip_smoke.Config()
    kw = dict(tile_size=cfg.tile_size, interpret=False)
    if name.endswith("_q"):
        kw["pad_len"] = cfg.tile_size
    fn = functools.partial(_KERNELS[name], **kw)
    if batched:
        fn = jax.vmap(fn)
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt,
                                                 sharding=one_chip)
    args = _args(name, cfg, sds, batch=(cfg.max_batch,) if batched else ())
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("name", ["chunk", "chunk_q"])
def test_chunk_kernels_compile_at_max_pad_len(one_chip, no_persistent_cache,
                                              chip_smoke, name):
    """The widest run the kernels accept still fits VMEM on a v5e."""
    import dataclasses
    cfg = dataclasses.replace(chip_smoke.Config(), tile_size=MAX_PAD_LEN)
    kw = dict(tile_size=MAX_PAD_LEN, interpret=False)
    if name == "chunk_q":
        kw["pad_len"] = MAX_PAD_LEN
    fn = jax.vmap(functools.partial(_KERNELS[name], **kw))
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt,
                                                 sharding=one_chip)
    args = _args(name, cfg, sds, batch=(cfg.max_batch,))
    assert jax.eval_shape(fn, *args).shape == (
        cfg.max_batch, cfg.chunk_tiles, 6, MAX_PAD_LEN)
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("name", ["tile", "chunk"])
@pytest.mark.parametrize("nq", [48, 16])
def test_fp32_kernels_compile_at_served_widths(one_chip, no_persistent_cache,
                                               chip_smoke, name, nq):
    """The fp32 kernels at the benchmark cells' long-route widths (48 and
    16 terms, ``pad_len`` 1,024, 8 batch rows of 8-tile chunks) compile
    natively with the six-row output the traversal's stats read."""
    import dataclasses
    cfg = dataclasses.replace(chip_smoke.Config(), query_terms=nq,
                              tile_size=1024, chunk_tiles=8, max_batch=8)
    fn = jax.vmap(functools.partial(_KERNELS[name], tile_size=1024,
                                    interpret=False))
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt,
                                                 sharding=one_chip)
    args = _args(name, cfg, sds, batch=(8,))
    want = (8, 8, 6, 1024) if name == "chunk" else (8, 6, 1024)
    assert jax.eval_shape(fn, *args).shape == want
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("name", ["chunk", "chunk_q"])
def test_chunk_kernels_keep_their_names_in_the_compiled_program(
        one_chip, no_persistent_cache, chip_smoke, name):
    """The kernel's instruction is named after its ``pallas_call`` name,
    not after whatever jitted function calls it, so a trace finds
    ``guided_score_chunk[_q]`` after any refactor of the callers."""
    import re
    cfg = chip_smoke.Config()
    kw = dict(tile_size=cfg.tile_size, interpret=False)
    if name == "chunk_q":
        kw["pad_len"] = cfg.tile_size

    def some_caller(*args):
        return jax.vmap(functools.partial(_KERNELS[name], **kw))(*args)
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt,
                                                 sharding=one_chip)
    args = _args(name, cfg, sds, batch=(cfg.max_batch,))
    text = jax.jit(some_caller).lower(*args).compile().as_text()
    calls = re.findall(r"%([\w.\-]+) = \S+ custom-call\(.*"
                       r'custom_call_target="tpu_custom_call"', text)
    assert [re.sub(r"\.\d+$", "", c) for c in calls] == [
        "guided_score_" + name]


def test_long_route_gather_fetches_windows_not_scalars(one_chip,
                                                       no_persistent_cache):
    """The benchmark cell's long-route gather (8 batch rows x 8 tiles of a
    chunk x 48 padded terms, ``pad_len`` 1,024 over a 1,105,228-passage
    shard's 131.5 M postings) fetches each (term, tile) run as one window.
    A ``gather`` with ``slice_sizes={1}`` over the flat posting arrays is
    one random fetch per slot, 3.1 M per array and chunk step; only the
    ``tile_ptr`` lookups (``slice_sizes={1,1}``) and the row gathers
    (``slice_sizes={1,128}``) may be gathers. No ``while`` loop either: a
    loop of one window copy a run writes three device events a trip, about
    27,650 a chunk step, more than a profiler trace keeps for a window."""
    import re
    from repro.core.index import flat_len
    from repro.core.traversal import _gather_tile
    rows, tiles, nq, pad_len, tile_size = 8, 8, 48, 1024, 1024
    nnz, n_terms, n_tiles = 131_500_000, 30522, 1080
    f32, i32 = jnp.float32, jnp.int32
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt,
                                                 sharding=one_chip)
    n = flat_len(nnz, pad_len)
    flat = (sds((n,), i32), sds((n,), f32), sds((n,), f32),
            sds((n_terms, n_tiles + 1), i32))

    def chunk_gather(docids, w_b, w_l, tile_ptr, qt, qwb, qwl, tiles_chunk):
        def row(qt, qwb, qwl, tc):
            return jax.vmap(lambda t: _gather_tile(
                docids, w_b, w_l, tile_ptr, qt, qwb, qwl, t,
                pad_len=pad_len, tile_size=tile_size))(tc)
        return jax.vmap(row)(qt, qwb, qwl, tiles_chunk)
    args = (*flat, sds((rows, nq), i32), sds((rows, nq), f32),
            sds((rows, nq), f32), sds((rows, tiles), i32))
    text = jax.jit(chunk_gather).lower(*args).compile().as_text()
    gathers = re.findall(r"= \S+ gather\(.*", text)
    assert gathers, "the tile_ptr lookups should compile to gathers"
    assert not [g for g in gathers if "slice_sizes={1}" in g]
    assert " while(" not in text
