"""The served path at k=1000 against the sequential oracle, on both routes.

A uniCOIL-like corpus (BM25-aligned, few expansion-only postings) is
served through ``AsyncRetrievalScheduler`` with the Table-8 policy: 2-4
live terms on the short route (jnp chunked traversal at width 4), longer
queries on the fused Pallas chunk kernel (interpret mode here) at width
16. One tile of 1,024 documents per visit, so every visit keeps
1,000-deep candidate lists. Some queries match fewer than k documents:
their lists end in sentinels (-1, -inf), never in documents that hold
none of the query's terms.
"""
import numpy as np
import pytest

from repro.core import build_index, twolevel
from repro.core.oracle import daat_2gti
from repro.data import make_corpus
from repro.serve import (AsyncRetrievalScheduler, SchedulerConfig,
                         table8_policy)

K = 1000
LENGTHS = (2, 3, 4, 5, 6, 7, 8, 9, 10, 12)


@pytest.fixture(scope="module")
def corpus():
    c = make_corpus("unicoil_like", n_docs=4500, n_terms=1024,
                    n_queries=len(LENGTHS), n_q_terms=12, n_rel=3,
                    avg_doc_terms=48, seed=23)
    merged = c.merged("scaled")
    df = np.diff(merged.indptr)
    # queries of every length, with their planted answers, plus on each
    # route one of rare terms (fewer than k matches) and one of common
    # terms (more than k)
    queries = [(c.queries[i][:n], c.q_weights_b[i][:n], c.q_weights_l[i][:n])
               for i, n in enumerate(LENGTHS)]
    rare = np.flatnonzero((df > 0) & (df < 60))
    common = np.flatnonzero((df > 600) & (df < 2000))
    rng = np.random.default_rng(5)
    for pool, n in ((rare, 3), (rare, 7), (common, 3), (common, 6)):
        t = rng.choice(pool, n, replace=False).astype(np.int32)
        queries.append((t, np.ones(n, np.float32),
                        (1.0 + rng.random(n)).astype(np.float32)))
    return merged, build_index(merged, tile_size=1024), queries


def _serve(index, params, queries):
    sched = AsyncRetrievalScheduler(
        index, params,
        SchedulerConfig(max_batch=4, pad_terms=16, cache_size=0,
                        executors=1),
        routing=table8_policy(short_max_len=4, long_engine="kernel",
                              long_traversal="chunked_fused"),
        k_buckets=(K,))
    sched.start()
    try:
        handles = [sched.submit(terms=t, weights_b=b, weights_l=l, k=K)
                   for t, b, l in queries]
        results = [h.result(timeout=600) for h in handles]
    finally:
        sched.close()
    return handles, results, sched.stats()


PRESETS = ("linear_combination", "fast")


@pytest.fixture(scope="module")
def runs(corpus):
    """{preset: (params, handles, results, stats)}: the rank-safe preset
    and 2GTI-Fast, each serving every query once."""
    _, index, queries = corpus
    out = {}
    for name in PRESETS:
        params = getattr(twolevel, name)()
        out[name] = (params, *_serve(index, params, queries))
    return out


def _n_match(merged, terms):
    return len(np.unique(np.concatenate(
        [merged.postings(int(t))[0] for t in terms])))


@pytest.mark.parametrize("preset", PRESETS)
def test_both_routes_serve_short_lists(corpus, runs, preset):
    """The cases this file is about occur: each route serves a query that
    matches fewer than k documents, and one that matches more."""
    merged, _, queries = corpus
    _, handles, _, _ = runs[preset]
    seen = set()
    for (t, _, _), h in zip(queries, handles):
        seen.add((h.route, _n_match(merged, t) < K))
    assert seen == {("short", True), ("short", False), ("long", True),
                    ("long", False)}


@pytest.mark.parametrize("preset", PRESETS)
def test_short_lists_end_in_sentinels(corpus, runs, preset):
    merged, _, queries = corpus
    _, _, results, _ = runs[preset]
    for (t, _, _), r in zip(queries, results):
        ids, scores = r.ids[0], r.scores[0]
        n = min(K, _n_match(merged, t))
        assert ids.shape == (K,)
        assert (ids[:n] >= 0).all() and np.isfinite(scores[:n]).all()
        assert (ids[n:] == -1).all() and np.isneginf(scores[n:]).all()
        assert len(np.unique(ids[:n])) == n
        assert (np.diff(scores[:n]) <= 0).all()


def test_rank_safe_answers_match_the_oracle(corpus, runs):
    merged, _, queries = corpus
    params, _, results, _ = runs["linear_combination"]
    for qi, ((t, b, l), r) in enumerate(zip(queries, results)):
        ids_o, vals_o, _ = daat_2gti(merged, t, b, l, params, k=K)
        np.testing.assert_array_equal(r.ids[0] >= 0, ids_o >= 0)
        live = ids_o >= 0
        np.testing.assert_allclose(r.scores[0][live], vals_o[live],
                                   rtol=1e-5, atol=1e-6)
        # ids agree except where adjacent scores tie
        mism = r.ids[0][live] != ids_o[live]
        v = vals_o[live]
        tied = np.zeros_like(mism)
        close = np.abs(np.diff(v)) <= 1e-5 * np.abs(v[1:])
        tied[1:] |= close
        tied[:-1] |= close
        assert not mism[~tied].any(), f"query {qi}"


def _allowed(merged, terms, qw_b, qw_l, ids, alpha, gamma):
    """[len(ids), m] RankScores 2GTI may return for each document: the
    sums over each leading run of the terms in descending list-bound
    order (the full RankScore is the longest run)."""
    lists = [merged.postings(int(t)) for t in terms]
    bound = np.array([alpha * b * (wb.max() if len(d) else 0.0)
                      + (1 - alpha) * l * (wl.max() if len(d) else 0.0)
                      for (d, wb, wl), b, l in zip(lists, qw_b, qw_l)])
    contrib = np.zeros((len(ids), len(terms)))
    for j, i in enumerate(np.argsort(-bound, kind="stable")):
        d, wb, wl = lists[i]
        at = np.minimum(np.searchsorted(d, ids), max(len(d) - 1, 0))
        hit = (d[at] == ids) if len(d) else np.zeros(len(ids), bool)
        contrib[hit, j] = (gamma * float(qw_b[i]) * wb[at[hit]]
                           + (1 - gamma) * float(qw_l[i]) * wl[at[hit]])
    return np.cumsum(contrib, axis=1)


def test_guided_scores_are_ones_2gti_allows(corpus, runs):
    merged, _, queries = corpus
    params, _, results, _ = runs["fast"]
    for qi, ((t, b, l), r) in enumerate(zip(queries, results)):
        live = r.ids[0] >= 0
        ids, scores = r.ids[0][live], r.scores[0][live]
        ok = _allowed(merged, t, b, l, ids, params.alpha, params.gamma)
        gap = np.abs(ok - scores[:, None].astype(np.float64)).min(1)
        assert gap.max() <= 1e-5 * scores.max(), f"query {qi}"


@pytest.mark.parametrize("preset", PRESETS)
def test_per_route_counters_add_up(runs, preset):
    """Each request's queue wait is counted under the route it was served
    on, each batch's device wait under its route, and the per-route
    counts add up to the global ones."""
    _, handles, _, stats = runs[preset]
    by_route = stats["by_route"]
    assert set(by_route) == {"short", "long"}
    for name, hists in by_route.items():
        served_here = sum(h.route == name for h in handles)
        assert hists["queue_wait_ms"]["n"] == served_here > 0
        assert hists["batch_device_ms"]["n"] > 0
        assert hists["batch_device_ms"]["mean"] > 0
    assert (sum(h["queue_wait_ms"]["n"] for h in by_route.values())
            == stats["queue_wait_ms"]["n"] == len(handles))
    assert (sum(h["batch_device_ms"]["n"] for h in by_route.values())
            == stats["batch_host_ms"]["n"] == stats["batches"])


@pytest.mark.parametrize("traversal,use_kernel", [("chunked", False),
                                                  ("chunked_fused", True)])
def test_padding_rows_dispatch_no_chunks(corpus, traversal, use_kernel):
    """A row of zero-weight slots (a batch's padding) matches nothing and
    keeps no chunk loop alive: every tile bound is -inf for it. A real
    row's work is the same beside it as alone."""
    from repro.core.traversal import retrieve_batched
    _, index, queries = corpus
    t, b, l = queries[-1]
    row = np.zeros((2, 16), np.int32), np.zeros((2, 16), np.float32)
    terms, wb, wl = row[0], row[1], row[1].copy()
    terms[1, :len(t)], wb[1, :len(t)], wl[1, :len(t)] = t, b, l
    params = twolevel.fast()
    both = retrieve_batched(index, terms, wb, wl, params, k=K,
                            use_kernel=use_kernel, traversal=traversal)
    alone = retrieve_batched(index, terms[1:], wb[1:], wl[1:], params, k=K,
                             use_kernel=use_kernel, traversal=traversal)
    assert both.stats["chunks_dispatched"][0] == 0
    assert (both.ids[0] == -1).all() and np.isneginf(both.scores[0]).all()
    assert both.stats["postings_touched"][0] == 0
    np.testing.assert_array_equal(both.ids[1], alone.ids[0])
    assert (both.stats["chunks_dispatched"][1]
            == alone.stats["chunks_dispatched"][0] > 0)


@pytest.mark.parametrize("traversal,use_kernel", [("chunked", False),
                                                  ("chunked_fused", True)])
def test_padding_leaves_k10_answers_unchanged(traversal, use_kernel):
    """At k=10 over a SPLADE-like corpus (the padding term 0 in most
    documents) a query padded with zero-weight slots returns exactly
    what it returns unpadded, with the same chunk count."""
    from repro.core.traversal import retrieve_batched
    c = make_corpus("splade_like", n_docs=3000, n_terms=512, n_queries=2,
                    n_q_terms=12, seed=31)
    index = build_index(c.merged("scaled"), tile_size=512)
    t, b, l = c.queries[0][:12], c.q_weights_b[0][:12], c.q_weights_l[0][:12]
    params = twolevel.accurate()
    out = []
    for width in (12, 16):
        terms = np.zeros((1, width), np.int32)
        wb, wl = np.zeros((2, 1, width), np.float32)
        terms[0, :12], wb[0, :12], wl[0, :12] = t, b, l
        out.append(retrieve_batched(index, terms, wb, wl, params, k=10,
                                    use_kernel=use_kernel,
                                    traversal=traversal))
    bare, padded = out
    np.testing.assert_array_equal(padded.ids, bare.ids)
    np.testing.assert_allclose(padded.scores, bare.scores, rtol=1e-6)
    assert (padded.stats["chunks_dispatched"][0]
            == bare.stats["chunks_dispatched"][0])
