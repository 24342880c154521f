"""CPU rehearsal of ``chip_smoke.py`` and the entry points' compile cache.

The smoke's phases run here at a tiny size with the Pallas interpreter, so a
wrong path, argument or check fails in seconds instead of on chip time. The
script itself must refuse to run without a TPU, and the compile-cache
helper must honour ``JAX_COMPILATION_CACHE_DIR`` and otherwise use the fixed
in-checkout directory.
"""
import dataclasses
import json
import os
import pathlib
import shutil
import subprocess
import sys
import textwrap

import pytest

from repro.launch import compile_cache

ROOT = pathlib.Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def tiny(chip_smoke):
    cfg = dataclasses.replace(
        chip_smoke.Config(), n_docs=2048, n_terms=512, tile_size=256,
        chunk_tiles=4, n_queries=4, query_terms=8, short_terms=3,
        n_requests=12, max_batch=4, exchange_every=2)
    corpus, merged, fp32, q8 = chip_smoke.index_phase(cfg)
    return cfg, corpus, merged, fp32, q8


def test_device_phase_refuses_cpu(chip_smoke, capsys):
    assert chip_smoke.device_phase(require_tpu=False)["platform"] == "cpu"
    with pytest.raises(SystemExit, match="no TPU"):
        chip_smoke.device_phase()


def test_index_phase_reports_geometry(tiny, capsys):
    cfg, corpus, merged, fp32, q8 = tiny
    assert fp32.n_tiles == cfg.n_docs // cfg.tile_size
    assert q8.pad_len == fp32.pad_len
    assert corpus.queries.shape == (cfg.n_queries, cfg.query_terms)


def test_kernels_phase_interpreted(chip_smoke, tiny, capsys):
    cfg, corpus, _, fp32, q8 = tiny
    chip_smoke.kernels_phase(cfg, corpus, fp32, q8, native=False)
    out = capsys.readouterr().out
    assert out.count("native=False") == 4
    assert "phase=kernels wall_s=" in out


def test_engines_phase(chip_smoke, tiny, capsys):
    cfg, corpus, merged, fp32, q8 = tiny
    chip_smoke.engines_phase(cfg, corpus, merged, {"fp32": fp32, "q8": q8})
    out = capsys.readouterr().out
    assert out.count("oracle_equal") == 2 * len(chip_smoke.KS)
    assert out.count("params=fast") == 4
    assert "phase=engines wall_s=" in out


def test_served_phase(chip_smoke, tiny, capsys):
    cfg, corpus, _, fp32, _ = tiny
    chip_smoke.served_phase(cfg, corpus, fp32)
    out = capsys.readouterr().out
    assert "compiles_after_warmup=0" in out
    assert "served ids equal direct Retriever.search" in out


def test_sharded_phase_one_device_mesh(chip_smoke, tiny, capsys):
    cfg, corpus, _, fp32, _ = tiny
    chip_smoke.sharded_phase(cfg, corpus, fp32, n_shards=1)
    out = capsys.readouterr().out
    assert out.count("ids_bit_identical=True") == 4


def test_rank_safe_check_rejects_wrong_ids(chip_smoke, tiny):
    """The oracle comparison is not vacuous: a ranking with one doc
    swapped for a worse-scoring one fails it."""
    cfg, corpus, merged, fp32, _ = tiny
    oracle = chip_smoke.Oracle(merged, corpus)
    ids, scores = oracle.ranked(0, 0.0, 10)
    chip_smoke.check_rank_safe([ids], [scores], oracle, 0.0, 10)
    worse, _ = oracle.ranked(0, 0.0, 40)
    bad = ids.copy()
    bad[3] = worse[-1]
    with pytest.raises(AssertionError, match="beyond ties"):
        chip_smoke.check_rank_safe([bad], [scores], oracle, 0.0, 10)


def _run(cmd, cwd, env_extra=None):
    env = {**os.environ, "JAX_PLATFORMS": "cpu", **(env_extra or {})}
    return subprocess.run(cmd, cwd=cwd, env=env, capture_output=True,
                          text=True, timeout=300)


def test_main_exits_nonzero_without_tpu():
    res = _run([sys.executable, "chip_smoke.py"], ROOT)
    assert res.returncode != 0
    assert '"ok"' not in res.stdout
    assert "no TPU" in res.stderr


def test_script_alone_exits_nonzero(tmp_path):
    shutil.copy(ROOT / "chip_smoke.py", tmp_path / "chip_smoke.py")
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    res = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                         env=env, capture_output=True, text=True,
                         timeout=300)
    assert res.returncode != 0
    assert '"ok"' not in res.stdout


def test_compile_cache_dir_honours_env(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.cache_dir() == tmp_path


def test_compile_cache_default_is_fixed_checkout_path(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert compile_cache.cache_dir() == ROOT / ".jax_cache"
    assert compile_cache.cache_dir() == compile_cache.cache_dir()


_CACHE_CHILD = textwrap.dedent("""
    import json, jax, jax.numpy as jnp
    from repro.launch.compile_cache import enable_compile_cache
    path = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.jit(lambda x: jnp.sin(x) * 3)(jnp.ones(7)).block_until_ready()
    print(json.dumps({"path": str(path),
                      "config": jax.config.jax_compilation_cache_dir}))
""")


def test_compile_cache_entries_land_in_env_dir(tmp_path):
    """With the variable set, the entry point writes its cache there and
    configures no directory of its own."""
    cache = tmp_path / "cache"
    res = _run([sys.executable, "-c", _CACHE_CHILD], tmp_path,
               {"JAX_COMPILATION_CACHE_DIR": str(cache),
                "PYTHONPATH": str(ROOT / "src")})
    assert res.returncode == 0, res.stderr[-2000:]
    out = json.loads(res.stdout.strip().splitlines()[-1])
    assert out["path"] == out["config"] == str(cache)
    assert any(cache.iterdir())
