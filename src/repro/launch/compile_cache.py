"""Persistent XLA compile cache for the repo's entry points.

``chip_smoke.py``, ``python -m repro.launch.serve`` and
``python -m benchmarks.run`` call :func:`enable_compile_cache` before they
compile anything, so a second run on the same machine reuses the serving
grid's compiled programs. Library imports and the test suite never call it.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already keeps its cache
there and nothing else is configured. Otherwise the cache lives at the
fixed ``<checkout>/.jax_cache``: the directory is part of each entry's key,
so a per-run temp name would never hit.
"""
from __future__ import annotations

import os
import pathlib

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def cache_dir() -> pathlib.Path:
    """Where the entry points keep the compile cache."""
    env = os.environ.get(ENV_VAR)
    return pathlib.Path(env) if env else DEFAULT_DIR


def enable_compile_cache() -> pathlib.Path:
    """Turn the persistent compile cache on; returns its directory."""
    import jax
    path = cache_dir()
    if ENV_VAR not in os.environ:
        jax.config.update("jax_compilation_cache_dir", str(path))
    return path
