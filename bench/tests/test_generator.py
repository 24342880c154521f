"""The generator against the program's presets, the reference against
the program's exhaustive and sequential 2GTI oracles, and the load
generator."""
import math

import numpy as np
import pytest

from lsrbench import gen, load
from lsrbench.reference import Reference
from repro.core.align import merge_models, misalignment_fraction
from repro.core import twolevel
from repro.core.oracle import daat_2gti, ranked_list
from repro.core.sparse import SparseModel
from repro.data import make_corpus as program_corpus

PRESETS = {
    "splade_like": dict(expansion_rate=0.92, weight_noise=0.55,
                        rel_on_expansion=0.75, avg_doc_terms=16),
    "unicoil_like": dict(expansion_rate=0.05, weight_noise=0.25,
                         rel_on_expansion=0.10, avg_doc_terms=96),
}


def cfg_for(preset, n_docs=1 << 14, n_terms=30522):
    return dict(PRESETS[preset], n_docs=n_docs, n_terms=n_terms,
                zipf_a=1.1, n_rel=4, n_distract=24)


def program_models(c):
    learned = SparseModel(c.n_docs, c.n_terms, c.indptr, c.docids, c.w_l)
    bm25 = SparseModel(c.n_docs, c.n_terms, *c.bm25_csr())
    return learned, bm25


@pytest.mark.parametrize("preset", sorted(PRESETS))
def test_generator_matches_preset_statistics(preset):
    cfg = cfg_for(preset)
    ours = gen.make_corpus(cfg, [32] * 16, seed=5)
    theirs = program_corpus(preset, n_docs=cfg["n_docs"], n_terms=30522,
                            n_queries=16, n_q_terms=32,
                            avg_doc_terms=cfg["avg_doc_terms"], seed=0)
    their_share = misalignment_fraction(theirs.learned, theirs.bm25)
    assert ours.expansion_share() == pytest.approx(their_share, abs=0.01)
    learned, bm25 = program_models(ours)
    assert misalignment_fraction(learned, bm25) == pytest.approx(
        ours.expansion_share(), abs=1e-9)
    per_doc = ours.nnz / cfg["n_docs"]
    their_per_doc = theirs.learned.nnz / cfg["n_docs"]
    assert per_doc == pytest.approx(their_per_doc, rel=0.03)
    bm25_per_doc = ours.in_b.sum() / cfg["n_docs"]
    assert bm25_per_doc == pytest.approx(theirs.bm25.nnz / cfg["n_docs"],
                                         rel=0.05)


def test_generator_is_a_pure_function_of_the_seed():
    cfg = cfg_for("splade_like", n_docs=1 << 12, n_terms=2048)
    a = gen.make_corpus(cfg, [3, 5, 8], seed=2**40 + 7, threads=1)
    b = gen.make_corpus(cfg, [3, 5, 8], seed=2**40 + 7, threads=4)
    c = gen.make_corpus(cfg, [3, 5, 8], seed=2**40 + 8, threads=4)
    for f in ("indptr", "docids", "w_l", "w_b", "in_b"):
        np.testing.assert_array_equal(getattr(a, f), getattr(b, f))
    assert not np.array_equal(a.w_l[:1000], c.w_l[:1000])
    keys = a.term_of().astype(np.int64) * a.n_docs + a.docids
    assert (np.diff(keys) > 0).all()           # sorted, unique postings
    assert (a.w_l > 0).all() and (a.w_b[a.in_b] > 0).all()
    assert [len(q[0]) for q in a.queries] == [3, 5, 8]


def test_relabelled_corpus_is_the_same_work_under_other_ids():
    cfg = cfg_for("splade_like", n_docs=4500, n_terms=1024)
    base = gen.make_corpus(cfg, [43, 43, 12], seed=7)
    a = gen.relabel_docs(base, 2**33 + 1, 512, threads=1)
    b = gen.relabel_docs(base, 2**33 + 2, 512, threads=3)
    np.testing.assert_array_equal(a.indptr, base.indptr)
    assert not np.array_equal(a.docids, b.docids)
    keys = a.term_of().astype(np.int64) * a.n_docs + a.docids
    assert (np.diff(keys) > 0).all()           # sorted, unique postings
    assert (a.docids[base.docids >= 4096] == base.docids[base.docids
                                                         >= 4096]).all()
    tile_of = base.term_of().astype(np.int64) * 9 + base.docids // 512
    for c in (a, b):
        c_tile = c.term_of().astype(np.int64) * 9 + c.docids // 512
        # every (term, tile) run keeps its size and its weights
        assert sorted(np.bincount(c_tile)) == sorted(np.bincount(tile_of))
        np.testing.assert_array_equal(np.sort(c.w_l), np.sort(base.w_l))
    for q in base.queries:
        sa = Reference(a, 1.0, 0.05).rank(*q, 10).top(10)
        sb = Reference(b, 1.0, 0.05).rank(*q, 10).top(10)
        np.testing.assert_allclose(sa[1], sb[1], rtol=1e-6)
        assert not np.array_equal(sa[0], sb[0])


@pytest.mark.parametrize("preset", sorted(PRESETS))
def test_reference_equals_program_oracle(preset):
    cfg = cfg_for(preset, n_docs=1 << 12, n_terms=2048)
    c = gen.make_corpus(cfg, [1, 4, 12, 32], seed=11)
    merged = merge_models(*program_models(c), "scaled")
    ref = Reference(c, alpha=1.0, gamma=0.05)
    np.testing.assert_array_equal(ref.w_b, merged.w_b)
    for terms, qb, ql in c.queries:
        for k in (10, 1000):
            o_ids, o_sc = ranked_list(merged, terms, qb, ql, 0.05, k)
            live = o_sc > 0
            ids, sc = ref.rank(terms, qb, ql, k).top(k)
            np.testing.assert_array_equal(ids, o_ids[live])
            np.testing.assert_allclose(sc, o_sc[live], rtol=1e-6)


@pytest.mark.parametrize("preset", ["accurate", "fast"])
def test_allowed_scores_hold_what_2gti_returns(preset):
    """Every score the paper's sequential 2GTI returns, partial RankScores
    of frozen documents among them, is one ``allowed`` names; the same
    scores lowered by a thousandth, or with a term or the BM25 part left
    out, are not. Thresholds over-estimated threefold (the paper's
    Table 3 knob) freeze documents that still reach the top 10."""
    cfg = cfg_for("splade_like", n_docs=1 << 12, n_terms=2048)
    c = gen.make_corpus(cfg, [4, 12, 32, 32, 43, 43], seed=29)
    merged = merge_models(*program_models(c), "scaled")
    params = getattr(twolevel, preset)(threshold_factor=3.0)
    ref = Reference(c, alpha=params.alpha, gamma=params.gamma)
    partial = 0
    for terms, qb, ql in c.queries:
        ids, sc, _ = daat_2gti(merged, terms, qb, ql, params, k=10)
        ids, sc = ids[ids >= 0], sc[ids >= 0].astype(np.float64)
        ok = ref.allowed(terms, qb, ql, ids)
        top1 = ref.rank(terms, qb, ql, 1).top(1)[1][0]
        gap = np.abs(ok - sc[:, None]).min(axis=1) / top1
        assert gap.max() < 1e-6
        partial += int((sc < ok[:, 0] - 1e-5 * top1).sum())
        assert (np.abs(ok - 0.999 * sc[:, None]).min(axis=1)
                / top1).max() > 1e-4
        no_bm25 = ref.allowed(terms, qb * 0, ql, ids)[:, 0]
        assert (np.abs(ok - no_bm25[:, None]).min(axis=1) / top1).max() > 1e-4
        without = ref.allowed(terms[1:], qb[1:], ql[1:], ids)[:, 0]
        assert (np.abs(ok - without[:, None]).min(axis=1) / top1).max() > 1e-4
    assert partial > 0


def test_due_times_are_the_same_work_for_every_seed():
    traffic = {"rate_qps": 4.0, "live_terms": {"values": [1, 2, 3, 4],
                                               "weights": [1, 3, 3, 2]}}
    a = load.due_times(traffic, 51)
    assert len(a) == 204
    assert (np.diff(a) > 0).all() and 0 < a[0] and a[-1] < 51
    np.testing.assert_array_equal(a, load.due_times(traffic, 51))
    gaps = np.diff(a, prepend=0)
    assert gaps.mean() == pytest.approx(0.25, rel=0.02)   # 4 per second
    assert 0.9 < gaps.std() / gaps.mean() < 1.1            # exponential
    ca = load.live_counts(traffic, 51)
    np.testing.assert_array_equal(ca, load.live_counts(traffic, 51))
    assert np.bincount(ca)[1:].tolist() == [23, 68, 68, 45]
    assert not np.array_equal(ca, np.sort(ca))              # shuffled


def test_failed_and_unfinished_requests_count_as_failed():
    t0 = 100.0
    outs = [load.Outcome(due=t0 + i, submitted=t0 + i, done=t0 + i + 0.5)
            for i in range(8)]
    outs[2].error = "shed"                      # refused or shed
    outs[5].done = math.nan
    outs[5].error = "no answer before the drain ended"
    s = load.summarize(outs, t0, 10.0)
    assert s["attempted"] == 8 and s["failed"] == 2 and s["completed"] == 6
    assert s["p50_ms"] == pytest.approx(500.0)
    assert s["qps"] == pytest.approx(6 / 10.0)
    late = load.summarize([load.Outcome(due=t0, submitted=t0,
                                        done=t0 + 30.0)], t0, 10.0)
    assert late["qps"] == pytest.approx(1 / 30.0)   # span runs to the end
