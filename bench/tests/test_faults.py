"""The comparison catches a broken timed path: each fault is planted in
the program underneath a whole run (at a tiny size on the CPU) and
``correct`` has to come out false."""
import json
import pathlib
import time

import jax
import numpy as np
import pytest

from lsrbench import cell
from repro.kernels import guided_score
from repro.retrieval.retriever import Retriever

CELLS = [w["name"] for w in json.loads(
    (pathlib.Path(__file__).resolve().parents[2] / "BENCHMARK.json")
    .read_text())["workloads"]]

REAL_SEARCH = Retriever.search
REAL_CHUNK = guided_score.guided_score_chunk


def _real_rows(kw):
    w = np.asarray(kw["weights_b"]) + np.asarray(kw["weights_l"])
    return np.flatnonzero((w != 0).any(axis=1))


def _on_answers(fault):
    """Plant ``fault(resp, kw)`` on every answer the retriever gives."""
    def plant(monkeypatch):
        def broken(self, request=None, **kw):
            resp = REAL_SEARCH(self, request, **kw)
            resp.ids = np.array(resp.ids)
            resp.scores = np.array(resp.scores)
            fault(resp, kw)
            return resp
        monkeypatch.setattr(Retriever, "search", broken)
    plant.__name__ = fault.__name__
    return plant


@_on_answers
def raise_score(resp, kw):
    resp.scores[_real_rows(kw), 0] *= np.float32(1.001)


@_on_answers
def lower_scores(resp, kw):
    resp.scores[_real_rows(kw)] *= np.float32(0.999)


@_on_answers
def alter_id(resp, kw):
    rows = _real_rows(kw)
    resp.ids[rows, 0] = resp.ids[rows, -1]      # an answer named twice


@_on_answers
def drop_half(resp, kw):
    rows = _real_rows(kw)
    out = rows[len(rows) // 2:]
    resp.ids[out] = -1
    resp.scores[out] = -np.inf


@_on_answers
def unchanged(resp, kw):
    resp.ids[:] = -1                            # the initial empty queue
    resp.scores[:] = -np.inf


def skip_a_term(monkeypatch):
    """Every query is scored without its first term."""
    def broken(self, request=None, **kw):
        for key in ("weights_b", "weights_l"):
            w = np.array(kw[key])
            w[:, 0] = 0
            kw[key] = w
        return REAL_SEARCH(self, request, **kw)
    monkeypatch.setattr(Retriever, "search", broken)


def drop_bm25_part(monkeypatch):
    """The chunk kernel's RankScore row loses its gamma * BM25 part (row
    0 holds the BM25 sum where alpha is 1, as in 2GTI-Accurate)."""
    def broken(offs, wb, wl, essential, prefix_beta, skip, th_lo, alpha,
               beta, gamma, **kw):
        out = REAL_CHUNK(offs, wb, wl, essential, prefix_beta, skip, th_lo,
                         alpha, beta, gamma, **kw)
        return out.at[:, 2].add(-gamma * out[:, 0])
    monkeypatch.setattr(guided_score, "guided_score_chunk", broken)


@pytest.fixture
def fresh_traces():
    """Programs traced with a planted fault never outlive their test."""
    jax.clear_caches()
    yield
    jax.clear_caches()


@pytest.mark.parametrize("fault", [raise_score, lower_scores, alter_id,
                                   drop_half, unchanged, skip_a_term,
                                   drop_bm25_part])
@pytest.mark.parametrize("workload", CELLS)
def test_planted_fault_is_not_correct(workload, fault, tiny, monkeypatch,
                                      fresh_traces):
    fault(monkeypatch)
    r = cell.run(workload, 77, 3.0, False, time.perf_counter(),
                 require_tpu=False, overrides=tiny)
    assert r["correct"] is False
    failing = [n for n, c in r["checks"].items() if c["value"] > c["limit"]]
    assert failing, r["checks"]
