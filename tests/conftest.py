"""Shared fixtures. NOTE: no XLA_FLAGS here — tests run on 1 CPU device;
only launch/dryrun.py (and its subprocess test) uses 512 fake devices."""
import importlib.util
import pathlib
import sys

import numpy as np
import pytest

from repro.data import make_corpus


@pytest.fixture(scope="session")
def small_corpus():
    return make_corpus("splade_like", n_docs=2048, n_terms=512,
                       n_queries=12, n_q_terms=5, n_rel=3,
                       avg_doc_terms=24, seed=7)


@pytest.fixture(scope="session")
def aligned_corpus():
    return make_corpus("unicoil_like", n_docs=2048, n_terms=512,
                       n_queries=8, n_q_terms=5, n_rel=3,
                       avg_doc_terms=24, seed=11)


def topk_scores_match(a_scores, b_scores, rtol=2e-5, atol=1e-4):
    np.testing.assert_allclose(a_scores, b_scores, rtol=rtol, atol=atol)


@pytest.fixture(scope="session")
def chip_smoke():
    """The repo-root ``chip_smoke.py`` script, imported as a module."""
    path = pathlib.Path(__file__).resolve().parents[1] / "chip_smoke.py"
    spec = importlib.util.spec_from_file_location("chip_smoke", path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod   # dataclasses resolve their module here
    spec.loader.exec_module(mod)
    return mod
