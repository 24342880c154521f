"""Test set-up: the program's sources and the benchmark's package on
the import path. These tests run on the CPU:

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests
"""
import pathlib
import sys

BENCH = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH.parent / "src"), str(BENCH)]

import pytest  # noqa: E402


@pytest.fixture
def tiny():
    """Overrides that shrink any cell to a size its whole machinery runs
    at on the CPU (the Pallas kernels in interpret mode), with a ragged
    last tile as the real shard has."""
    return {"corpus.n_docs": 4500, "corpus.n_terms": 1024,
            "index.tile_size": 512, "traffic.rate_qps": 2.0}
