"""Integration tests: tile-scan engine vs exhaustive scoring and the
sequential numpy DAAT oracle; execution-mode and scheduling equivalences."""
import numpy as np
import pytest

from repro.core import build_index, twolevel
from repro.core.index import impact_doc_order
from repro.core.metrics import evaluate_run
from repro.core.oracle import daat_2gti, ranked_list
from repro.core.traversal import retrieve_batched, retrieve_sequential


@pytest.fixture(scope="module")
def setup(small_corpus):
    merged = small_corpus.merged("scaled")
    index = build_index(merged, tile_size=256)
    return small_corpus, merged, index


def _q(corpus, qi):
    return (corpus.queries[qi], corpus.q_weights_b[qi],
            corpus.q_weights_l[qi])


@pytest.mark.parametrize("gamma", [0.0, 0.3, 1.0])
def test_rank_safe_config_equals_exhaustive(setup, gamma):
    """alpha=beta=gamma: pruning is bound-exact for the combined score."""
    corpus, merged, index = setup
    p = twolevel.original(gamma=gamma)
    res = retrieve_batched(index, corpus.queries, corpus.q_weights_b,
                           corpus.q_weights_l, p)
    for qi in range(len(corpus.queries)):
        ids_ref, vals_ref = ranked_list(merged, *_q(corpus, qi), gamma, 10)
        np.testing.assert_allclose(res.scores[qi], vals_ref,
                                   rtol=2e-5, atol=1e-4)


@pytest.mark.parametrize("schedule", ["docid", "impact"])
def test_sequential_equals_batched(setup, schedule):
    corpus, merged, index = setup
    p = twolevel.fast().replace(schedule=schedule)
    res_b = retrieve_batched(index, corpus.queries, corpus.q_weights_b,
                             corpus.q_weights_l, p)
    res_s = retrieve_sequential(index, corpus.queries[:4],
                                corpus.q_weights_b[:4],
                                corpus.q_weights_l[:4], p)
    np.testing.assert_array_equal(res_s.ids, res_b.ids[:4])
    np.testing.assert_allclose(res_s.scores, res_b.scores[:4], rtol=1e-6)


def test_impact_schedule_rank_safe_set_equality(setup):
    """Visit order must not change results for a rank-safe config."""
    corpus, merged, index = setup
    p0 = twolevel.original(gamma=0.2)
    p1 = p0.replace(schedule="impact")
    r0 = retrieve_batched(index, corpus.queries, corpus.q_weights_b,
                          corpus.q_weights_l, p0)
    r1 = retrieve_batched(index, corpus.queries, corpus.q_weights_b,
                          corpus.q_weights_l, p1)
    np.testing.assert_allclose(r0.scores, r1.scores, rtol=1e-6)
    assert all(set(a) == set(b) for a, b in zip(r0.ids, r1.ids))


def test_doc_reordering_preserves_rank_safe_results(setup):
    corpus, merged, index = setup
    order = impact_doc_order(merged)
    index_r = build_index(merged, tile_size=256, doc_order=order)
    p = twolevel.original(gamma=0.2)
    r0 = retrieve_batched(index, corpus.queries, corpus.q_weights_b,
                          corpus.q_weights_l, p)
    r1 = retrieve_batched(index_r, corpus.queries, corpus.q_weights_b,
                          corpus.q_weights_l, p)
    np.testing.assert_allclose(r0.scores, r1.scores, rtol=1e-6)
    assert all(set(a) == set(b) for a, b in zip(r0.ids, r1.ids))


def test_gti_is_special_case_alpha_beta_one(setup):
    corpus, merged, index = setup
    gti = twolevel.gti(gamma=0.1)
    manual = twolevel.TwoLevelParams(alpha=1.0, beta=1.0, gamma=0.1)
    r0 = retrieve_batched(index, corpus.queries, corpus.q_weights_b,
                          corpus.q_weights_l, gti)
    r1 = retrieve_batched(index, corpus.queries, corpus.q_weights_b,
                          corpus.q_weights_l, manual)
    np.testing.assert_array_equal(r0.ids, r1.ids)


def test_engine_matches_oracle_relevance(setup):
    """Tile engine prunes lazily vs per-doc DAAT: relevance metrics match."""
    corpus, merged, index = setup
    p = twolevel.fast()
    res = retrieve_batched(index, corpus.queries, corpus.q_weights_b,
                           corpus.q_weights_l, p)
    oracle_ids = np.array([daat_2gti(merged, *_q(corpus, qi), p)[0]
                           for qi in range(len(corpus.queries))])
    m_e = evaluate_run(res.ids, corpus.qrels, 10)
    m_o = evaluate_run(oracle_ids, corpus.qrels, 10)
    assert abs(m_e["mrr"] - m_o["mrr"]) < 0.05
    assert m_e["recall"] >= m_o["recall"] - 0.05


def test_overestimation_prunes_more_and_degrades(setup):
    """Table 3: threshold over-estimation trades relevance for pruning."""
    corpus, merged, index = setup
    base = twolevel.original(gamma=0.0)
    over = base.replace(threshold_factor=1.5)
    r_base = retrieve_batched(index, corpus.queries, corpus.q_weights_b,
                              corpus.q_weights_l, base)
    r_over = retrieve_batched(index, corpus.queries, corpus.q_weights_b,
                              corpus.q_weights_l, over)
    assert (r_over.stats["docs_survived"].mean()
            <= r_base.stats["docs_survived"].mean())
    m_b = evaluate_run(r_base.ids, corpus.qrels, 10)
    m_o = evaluate_run(r_over.ids, corpus.qrels, 10)
    assert m_o["recall"] <= m_b["recall"] + 1e-9


def test_guided_prunes_more_than_unguided(small_corpus):
    """BM25 guidance must create skipping the learned weights cannot.

    Uses the zero-filled index: there BM25's skewed weight distribution is
    undiluted, the regime where the paper observes GT/GTI's pruning power.
    """
    corpus = small_corpus
    index = build_index(corpus.merged("zero"), tile_size=256)
    r_org = retrieve_batched(index, corpus.queries, corpus.q_weights_b,
                             corpus.q_weights_l, twolevel.original())
    r_gti = retrieve_batched(index, corpus.queries, corpus.q_weights_b,
                             corpus.q_weights_l, twolevel.gti())
    assert (r_gti.stats["docs_survived"].mean()
            < r_org.stats["docs_survived"].mean())


def test_stats_are_consistent(setup):
    corpus, merged, index = setup
    res = retrieve_batched(index, corpus.queries, corpus.q_weights_b,
                           corpus.q_weights_l, twolevel.fast())
    s = res.stats
    assert np.all(s["docs_survived"] <= s["docs_present"])
    assert np.all(s["docs_frozen"] <= s["docs_survived"])
    assert np.all(s["tiles_visited"] <= s["n_tiles"])


def test_k_larger_than_matches(setup):
    corpus, merged, index = setup
    p = twolevel.fast()
    res = retrieve_batched(index, corpus.queries[:2], corpus.q_weights_b[:2],
                           corpus.q_weights_l[:2], p, k=500)
    assert res.ids.shape == (2, 500)
    # padded tail exists but scored entries are sorted desc
    sc = res.scores[0]
    finite = sc[np.isfinite(sc)]
    assert np.all(np.diff(finite) <= 1e-6)


def test_kernel_path_matches_jnp_path(setup):
    """Engine with the fused Pallas guided_score kernel (interpret mode)
    must match the pure-jnp tile scorer exactly."""
    corpus, merged, index = setup
    p = twolevel.fast()
    r_jnp = retrieve_batched(index, corpus.queries[:4],
                             corpus.q_weights_b[:4],
                             corpus.q_weights_l[:4], p)
    r_ker = retrieve_batched(index, corpus.queries[:4],
                             corpus.q_weights_b[:4],
                             corpus.q_weights_l[:4], p, use_kernel=True)
    np.testing.assert_array_equal(r_jnp.ids, r_ker.ids)
    np.testing.assert_allclose(r_jnp.scores, r_ker.scores, rtol=1e-6)


# -- chunked traversal: real skipping under jit -------------------------------

PARITY_STATS = ("tiles_visited", "docs_present", "docs_survived",
                "docs_frozen", "postings_touched")


def _assert_identical(full, chunked):
    np.testing.assert_array_equal(full.ids, chunked.ids)
    np.testing.assert_array_equal(full.scores, chunked.scores)
    for key in PARITY_STATS:
        np.testing.assert_array_equal(full.stats[key], chunked.stats[key])


def test_chunk_schedule_covers_all_tiles(setup):
    """The chunk order is a permutation of all tiles (plus the sentinel
    tail padding) with descending per-chunk max bounds."""
    import jax.numpy as jnp
    from repro.core.plan import chunk_schedule, plan_query
    corpus, merged, index = setup
    plan = plan_query(jnp.asarray(corpus.queries[0]),
                      jnp.asarray(corpus.q_weights_b[0]),
                      jnp.asarray(corpus.q_weights_l[0]),
                      index.sigma_b, index.sigma_l, jnp.float32(1.0))
    sched = chunk_schedule(plan, index.tile_max_b, index.tile_max_l,
                           jnp.float32(1.0), index.n_tiles, 3)
    chunks = np.asarray(sched.chunks)
    assert chunks.shape == (-(-index.n_tiles // 3), 3)
    real = chunks[chunks < index.n_tiles]
    np.testing.assert_array_equal(np.sort(real), np.arange(index.n_tiles))
    assert (chunks[chunks >= index.n_tiles] == index.n_tiles).all()
    ub = np.asarray(sched.chunk_ub)
    assert (np.diff(ub) <= 0).all()


@pytest.mark.parametrize("use_kernel", [False, True],
                         ids=["jnp", "pallas_kernel"])
@pytest.mark.parametrize("preset", ["rank_safe", "guided"])
def test_chunked_bit_identical_to_full_scan(setup, preset, use_kernel):
    """traversal='chunked' visits the descending-bound order, so it must
    be bit-identical — ids, scores, and every pruning stat — to the full
    impact-schedule scan, for rank-safe and guided configs alike."""
    corpus, merged, index = setup
    p = (twolevel.original(gamma=0.2) if preset == "rank_safe"
         else twolevel.fast()).replace(chunk_tiles=2)
    full = retrieve_batched(index, corpus.queries, corpus.q_weights_b,
                            corpus.q_weights_l,
                            p.replace(schedule="impact"),
                            use_kernel=use_kernel)
    ck = retrieve_batched(index, corpus.queries, corpus.q_weights_b,
                          corpus.q_weights_l, p, traversal="chunked",
                          use_kernel=use_kernel)
    _assert_identical(full, ck)
    assert "chunks_dispatched" in ck.stats
    assert (ck.stats["chunks_dispatched"] <= ck.stats["n_chunks"]).all()


def test_chunked_early_exit_dispatches_fewer_chunks(small_corpus):
    """On a guided config whose full scan skips tiles, the chunk loop must
    stop early: strictly fewer chunks dispatched than n_chunks, while
    results stay bit-identical to the full impact scan."""
    corpus = small_corpus
    index = build_index(corpus.merged("scaled"), tile_size=64)  # 32 tiles
    p = twolevel.gti().replace(chunk_tiles=4)                   # 8 chunks
    full = retrieve_batched(index, corpus.queries, corpus.q_weights_b,
                            corpus.q_weights_l,
                            p.replace(schedule="impact"))
    ck = retrieve_batched(index, corpus.queries, corpus.q_weights_b,
                          corpus.q_weights_l, p, traversal="chunked")
    _assert_identical(full, ck)
    # the full scan skips tiles here, so the chunk loop must exit early:
    # strictly fewer chunks dispatched than the grid holds, in aggregate
    # and for most queries (a query that never converges keeps its own
    # count at n_chunks; the batch-level reduction is the contract)
    assert (full.stats["tiles_visited"] < full.stats["n_tiles"]).any()
    disp, n_chunks = ck.stats["chunks_dispatched"], ck.stats["n_chunks"]
    assert disp.sum() < n_chunks.sum()
    assert (disp < n_chunks).mean() > 0.5
    # dispatched chunks at least cover the visited tiles
    assert (disp * p.chunk_tiles >= ck.stats["tiles_visited"]).all()


def test_chunked_fused_kernel_rank_safe_exact(setup):
    """The multi-tile guided_score_chunk kernel scores with chunk-start
    thresholds — for rank-safe configs that is still bound-exact, so the
    top-k must match the full impact scan bit-for-bit."""
    corpus, merged, index = setup
    p = twolevel.original(gamma=0.2).replace(chunk_tiles=2)
    full = retrieve_batched(index, corpus.queries, corpus.q_weights_b,
                            corpus.q_weights_l,
                            p.replace(schedule="impact"))
    fu = retrieve_batched(index, corpus.queries, corpus.q_weights_b,
                          corpus.q_weights_l, p, traversal="chunked_fused",
                          use_kernel=True)
    np.testing.assert_array_equal(full.ids, fu.ids)
    np.testing.assert_allclose(full.scores, fu.scores, rtol=1e-6)


def test_chunked_fused_guided_tolerance(small_corpus):
    """Guided configs under the fused chunk kernel: chunk-start thresholds
    shift the pruning trajectory (looser within a chunk, so the queues
    tighten faster across chunks) — the usual guided tolerance. At the
    default threshold_factor the trajectories coincide on this corpus
    (pinned as a regression, like the sharded guided parity test); under
    aggressive over-estimation heads must still agree almost everywhere."""
    corpus = small_corpus
    index = build_index(corpus.merged("scaled"), tile_size=64)
    q = (corpus.queries, corpus.q_weights_b, corpus.q_weights_l)
    p = twolevel.fast().replace(chunk_tiles=4)
    ck = retrieve_batched(index, *q, p, traversal="chunked")
    fu = retrieve_batched(index, *q, p, traversal="chunked_fused",
                          use_kernel=True)
    np.testing.assert_array_equal(ck.ids, fu.ids)
    np.testing.assert_allclose(ck.scores, fu.scores, rtol=1e-5, atol=1e-4)

    p_over = twolevel.fast(threshold_factor=1.5).replace(chunk_tiles=4)
    ck = retrieve_batched(index, *q, p_over, traversal="chunked")
    fu = retrieve_batched(index, *q, p_over, traversal="chunked_fused",
                          use_kernel=True)
    overlap = np.mean([len(set(a) & set(b)) / len(a)
                       for a, b in zip(ck.ids, fu.ids)])
    assert overlap > 0.9


@pytest.mark.parametrize("traversal", ["chunked_fused", "chunked"],
                         ids=["chunk_kernel", "tile_kernel"])
@pytest.mark.parametrize("preset", ["rank_safe", "guided"])
def test_kernel_stats_bit_identical_to_jnp_counts(setup, preset, traversal):
    """The kernel paths read ``docs_present`` and ``postings_touched`` from
    the kernel's per-slot posting count; they must equal the jnp scorer's
    scatter counts bit for bit. At one tile a chunk the fused kernel's
    chunk-start thresholds are the per-tile ones, so its trajectory is the
    jnp chunk loop's."""
    corpus, merged, index = setup
    p = (twolevel.original(gamma=0.2) if preset == "rank_safe"
         else twolevel.fast()).replace(chunk_tiles=1)
    q = (corpus.queries, corpus.q_weights_b, corpus.q_weights_l)
    ck = retrieve_batched(index, *q, p, traversal="chunked")
    ker = retrieve_batched(index, *q, p, traversal=traversal,
                           use_kernel=True)
    np.testing.assert_array_equal(ck.ids, ker.ids)
    np.testing.assert_allclose(ck.scores, ker.scores, rtol=1e-6)
    for key in PARITY_STATS + ("chunks_dispatched",):
        np.testing.assert_array_equal(ck.stats[key], ker.stats[key])
    assert (ker.stats["docs_present"] > 0).all()
    assert (ker.stats["postings_touched"] >= ker.stats["docs_present"]).all()


def test_chunked_rejects_unknown_traversal(setup):
    corpus, merged, index = setup
    with pytest.raises(ValueError, match="traversal"):
        retrieve_batched(index, corpus.queries, corpus.q_weights_b,
                         corpus.q_weights_l, twolevel.fast(),
                         traversal="tiled")


def test_chunk_tiles_argument_overrides_params(setup):
    """The per-call chunk_tiles override changes the chunk grid but not
    the results (both are the same descending-order traversal)."""
    corpus, merged, index = setup
    p = twolevel.fast()  # default chunk_tiles=8 -> 1 chunk on 8 tiles
    r8 = retrieve_batched(index, corpus.queries, corpus.q_weights_b,
                          corpus.q_weights_l, p, traversal="chunked")
    r2 = retrieve_batched(index, corpus.queries, corpus.q_weights_b,
                          corpus.q_weights_l, p, traversal="chunked",
                          chunk_tiles=2)
    _assert_identical(r8, r2)
    assert r8.stats["n_chunks"][0] == 1.0
    assert r2.stats["n_chunks"][0] == 4.0
