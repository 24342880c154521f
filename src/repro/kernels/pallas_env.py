"""Pallas execution-mode selection shared by every kernel in this package.

``interpret=None`` (the kernels' default) resolves per process: native
lowering iff the default backend is a TPU, the Python interpreter on every
other backend (the CPU test suite).
"""
from __future__ import annotations


def default_interpret() -> bool:
    """True = run kernels under the Pallas interpreter (non-TPU backends)."""
    import jax
    return jax.default_backend() != "tpu"
