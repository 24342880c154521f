"""Jit'd public wrappers for the Pallas kernels.

Every kernel takes ``interpret=None`` and resolves it per process via
``pallas_env.default_interpret``: native lowering when the default
backend is a TPU, the Python interpreter on every other backend.
"""
from __future__ import annotations

from .embedding_bag import embedding_bag  # noqa: F401
from .flash_attention import flash_attention  # noqa: F401
from .guided_score import guided_score_chunk, guided_score_tile  # noqa: F401
from .pallas_env import default_interpret  # noqa: F401
