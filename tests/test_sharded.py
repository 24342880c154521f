"""Sharded retrieval: shard-plan construction invariants and sharded-vs-
single-device parity.

Fast lane runs on the 1 CPU device: the vmap emulation path executes the
identical per-shard scan + merge math as the ``shard_map`` path for any
shard count, and a 1-device mesh exercises the real shard_map plumbing at
n_shards=1. The slow lane spawns a subprocess with 8 fake host devices and
pins the full collective path (ring-gather merge, threshold exchange,
Pallas scorer) bit-identical to both the emulation path and single-device
``retrieve_batched``."""
import json
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro.core import build_index, twolevel
from repro.core.shard_plan import shard_index
from repro.core.traversal import retrieve_batched
from repro.serve.sharded import make_shard_mesh, shard_retrieve_batched

K = 10


@pytest.fixture(scope="module")
def setup(small_corpus):
    merged = small_corpus.merged("scaled")
    index = build_index(merged, tile_size=256)  # 2048 docs -> 8 tiles
    return small_corpus, index


def _q(corpus):
    return corpus.queries, corpus.q_weights_b, corpus.q_weights_l


# -- shard plan construction --------------------------------------------------

@pytest.mark.parametrize("n_shards", [1, 2, 3, 4, 8, 16])
def test_shard_plan_repacks_every_posting(setup, n_shards):
    """Per-shard slabs partition the postings: rebasing each shard's local
    docids by its doc_base and re-sorting (term, docid) recovers exactly
    the original flat arrays — nothing lost, duplicated, or re-weighted."""
    corpus, index = setup
    sh = shard_index(index, n_shards)
    assert sh.nnz_per_shard.sum() == index.nnz
    assert n_shards * sh.tiles_per_shard >= index.n_tiles
    doc_base = np.asarray(sh.doc_base)
    ptr = np.asarray(sh.tile_ptr)
    got = []
    for s in range(n_shards):
        nnz = int(sh.nnz_per_shard[s])
        docs = np.asarray(sh.docids[s][:nnz]) + doc_base[s]
        wb = np.asarray(sh.w_b[s][:nnz])
        wl = np.asarray(sh.w_l[s][:nnz])
        # term of each local posting from the local tile_ptr row bounds
        term_of = np.repeat(np.arange(index.n_terms),
                            ptr[s, :, -1] - ptr[s, :, 0])
        got.append(np.stack([term_of, docs, wb, wl]))
    term_of, docs, wb, wl = np.concatenate(got, axis=1)
    order = np.lexsort((docs, term_of))
    real = slice(0, index.nnz)  # the flat arrays' sentinel tail is not repacked
    np.testing.assert_array_equal(docs[order], np.asarray(index.docids)[real])
    np.testing.assert_array_equal(wb[order], np.asarray(index.w_b)[real])
    np.testing.assert_array_equal(wl[order], np.asarray(index.w_l)[real])


def test_shard_plan_padded_tiles_are_empty(setup):
    """n_shards that don't divide n_tiles pad the tail shard: padded tiles
    carry zero postings and zero block maxima."""
    corpus, index = setup
    sh = shard_index(index, 3)  # 8 tiles -> tps=3, last shard 2 real + 1 pad
    assert sh.tiles_per_shard == 3
    ptr = np.asarray(sh.tile_ptr[2])
    assert np.all(ptr[:, -1] == ptr[:, -2])  # pad tile: empty runs
    assert float(np.asarray(sh.tile_max_b[2][:, -1]).max()) == 0.0
    assert float(np.asarray(sh.tile_max_l[2][:, -1]).max()) == 0.0


# -- parity: emulation path (any shard count on 1 device) ---------------------

@pytest.mark.parametrize("use_kernel", [False, True],
                         ids=["jnp", "pallas_kernel"])
@pytest.mark.parametrize("schedule", ["docid", "impact"])
def test_single_shard_exact_parity_guided(setup, schedule, use_kernel):
    """n_shards=1 is the same traversal: any config matches bit-exactly."""
    corpus, index = setup
    p = twolevel.fast().replace(schedule=schedule)
    ref = retrieve_batched(index, *_q(corpus), p, use_kernel=use_kernel)
    res = shard_retrieve_batched(shard_index(index, 1), *_q(corpus), p,
                                 use_kernel=use_kernel)
    np.testing.assert_array_equal(res.ids, ref.ids)
    np.testing.assert_array_equal(res.scores, ref.scores)


@pytest.mark.parametrize("use_kernel", [False, True],
                         ids=["jnp", "pallas_kernel"])
@pytest.mark.parametrize("schedule", ["docid", "impact"])
@pytest.mark.parametrize("n_shards", [2, 4])
def test_multi_shard_rank_safe_exact_parity(setup, n_shards, schedule,
                                            use_kernel):
    """Rank-safe configs: pruning is bound-exact, so tile-range sharding
    (a traversal-order change) must return bit-identical top-k."""
    corpus, index = setup
    p = twolevel.original(gamma=0.2).replace(schedule=schedule)
    ref = retrieve_batched(index, *_q(corpus), p, use_kernel=use_kernel)
    res = shard_retrieve_batched(shard_index(index, n_shards), *_q(corpus),
                                 p, use_kernel=use_kernel)
    np.testing.assert_array_equal(res.ids, ref.ids)
    np.testing.assert_array_equal(res.scores, ref.scores)


@pytest.mark.parametrize("schedule", ["docid", "impact"])
def test_multi_shard_guided_parity(setup, schedule):
    """Guided configs prune against order-dependent thresholds, so shard-
    local thresholds are only *looser* (never unsafe). On this corpus the
    kept sets coincide, pinning the merge end-to-end for unsafe configs."""
    corpus, index = setup
    p = twolevel.fast().replace(schedule=schedule)
    ref = retrieve_batched(index, *_q(corpus), p)
    res = shard_retrieve_batched(shard_index(index, 4), *_q(corpus), p)
    np.testing.assert_array_equal(res.ids, ref.ids)
    np.testing.assert_array_equal(res.scores, ref.scores)


def test_multi_shard_guided_scores_dominate(setup):
    """The corpus-robust guided invariant: a shard's local theta trajectory
    is always <= the single-device one (its queue saw a subset of tiles),
    so every doc freezes no earlier and every returned score dominates
    elementwise. threshold_factor=1.5 forces aggressive pruning so the
    trajectories actually diverge."""
    corpus, index = setup
    p = twolevel.fast().replace(threshold_factor=1.5)
    ref = retrieve_batched(index, *_q(corpus), p)
    res = shard_retrieve_batched(shard_index(index, 4), *_q(corpus), p)
    assert np.all(res.scores >= ref.scores - 1e-5)


def test_threshold_exchange_rank_safe_exact(setup):
    """The exchanged floor is the exact global theta — a safe bound — so
    rank-safe results stay bit-identical at any exchange period."""
    corpus, index = setup
    p = twolevel.original(gamma=0.2)
    ref = retrieve_batched(index, *_q(corpus), p)
    sh = shard_index(index, 4)
    for every in (1, 2):
        res = shard_retrieve_batched(sh, *_q(corpus), p,
                                     exchange_every=every)
        np.testing.assert_array_equal(res.ids, ref.ids)
        np.testing.assert_array_equal(res.scores, ref.scores)


def test_fine_exchange_beyond_former_round_cap(small_corpus):
    """exchange_every=1 at 256 tiles (128 rounds/shard) — double the old
    64-segment unroll cap — compiles as one lax.scan over sentinel-padded
    rounds and stays bit-identical for rank-safe configs."""
    corpus = small_corpus
    index = build_index(corpus.merged("scaled"), tile_size=8)  # 256 tiles
    p = twolevel.original(gamma=0.2)
    ref = retrieve_batched(index, *_q(corpus), p)
    res = shard_retrieve_batched(shard_index(index, 2), *_q(corpus), p,
                                 exchange_every=1)
    np.testing.assert_array_equal(res.ids, ref.ids)
    np.testing.assert_array_equal(res.scores, ref.scores)


def test_exchange_round_sentinel_padding_parity(setup):
    """Periods that don't divide tiles_per_shard exercise the sentinel
    tile: it must touch no queue or stat (tiles_visited unchanged)."""
    corpus, index = setup
    p = twolevel.original(gamma=0.2)
    sh = shard_index(index, 3)  # 8 tiles -> 3 tiles/shard
    ref = retrieve_batched(index, *_q(corpus), p)
    for every in (2, 4):  # 2: padded tail round; 4 > tps: single round
        res = shard_retrieve_batched(sh, *_q(corpus), p,
                                     exchange_every=every)
        np.testing.assert_array_equal(res.ids, ref.ids)
        np.testing.assert_array_equal(res.scores, ref.scores)
        np.testing.assert_allclose(res.stats["shard_tiles_visited"].sum(1),
                                   res.stats["tiles_visited"])


def test_one_device_mesh_equals_emulation(setup):
    """The real shard_map path on the 1-device mesh == the vmap path."""
    corpus, index = setup
    p = twolevel.fast()
    sh = shard_index(index, 1)
    emu = shard_retrieve_batched(sh, *_q(corpus), p)
    msh = shard_retrieve_batched(sh, *_q(corpus), p, mesh=make_shard_mesh(1))
    np.testing.assert_array_equal(msh.ids, emu.ids)
    np.testing.assert_array_equal(msh.scores, emu.scores)


def test_engine_places_shards_on_mesh(setup):
    """Opening the sharded engine on a mesh lays every stacked leaf out
    over the shard axis once (NamedSharding), and serving from the placed
    index equals the emulation path."""
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from repro.retrieval import Retriever
    corpus, index = setup
    p = twolevel.fast()
    retr = Retriever.open(index, p, "sharded", mesh=make_shard_mesh(1))
    sh = retr.engine.sharded
    for leaf in (*sh.gather, sh.tile_max_b, sh.doc_base):
        assert isinstance(leaf.sharding, NamedSharding)
        assert leaf.sharding.spec == P("shard", *([None] * (leaf.ndim - 1)))
    assert sh.sigma_b.sharding.spec == P()
    assert retr.replicate().engine.sharded.gather[0] is sh.gather[0]
    emu = shard_retrieve_batched(shard_index(index, 1), *_q(corpus), p)
    got = retr.search(terms=corpus.queries, weights_b=corpus.q_weights_b,
                      weights_l=corpus.q_weights_l, k=K)
    np.testing.assert_array_equal(got.ids, emu.ids)


def test_launch_serve_refuses_more_shards_than_devices(monkeypatch, capsys):
    """``--shards N`` needs N devices: no silent single-device fallback."""
    from repro.launch import serve
    monkeypatch.setattr(sys, "argv", ["serve", "--shards", "4"])
    with pytest.raises(SystemExit) as exc:
        serve.main()
    assert exc.value.code == 2
    assert "needs 4 devices" in capsys.readouterr().err


def test_launch_serve_exits_nonzero_when_requests_fail(monkeypatch):
    """A run whose batches all fail must not exit 0."""
    from repro.launch import compile_cache, serve
    from repro.retrieval import Retriever

    def broken(self, *a, **kw):
        raise RuntimeError("engine down")
    monkeypatch.setattr(Retriever, "search", broken)
    # the test process keeps its compile cache off
    monkeypatch.setattr(compile_cache, "enable_compile_cache", lambda: None)
    monkeypatch.setattr(sys, "argv", ["serve", "--docs", "2048",
                                      "--requests", "4", "--qps", "1000"])
    with pytest.raises(SystemExit) as exc:
        serve.main()
    assert "4 of 4 requests failed" in str(exc.value.code)


# -- chunked per-shard traversal ----------------------------------------------

@pytest.mark.parametrize("exchange_every", [0, 2])
@pytest.mark.parametrize("n_shards", [1, 3, 4])
def test_sharded_chunked_bit_identical_to_full_impact(setup, n_shards,
                                                      exchange_every):
    """Per-shard chunk loops visit each shard's tiles in descending-bound
    order — bit-identical to the impact-schedule full sharded scan (ids,
    scores, tiles_visited), shape-padding tiles included (n_shards=3 pads
    the tail shard). chunks_dispatched never exceeds the chunk grid."""
    corpus, index = setup
    p = twolevel.fast().replace(chunk_tiles=2)
    sh = shard_index(index, n_shards)
    full = shard_retrieve_batched(sh, *_q(corpus),
                                  p.replace(schedule="impact"),
                                  exchange_every=exchange_every)
    ck = shard_retrieve_batched(sh, *_q(corpus), p, traversal="chunked",
                                exchange_every=exchange_every)
    np.testing.assert_array_equal(full.ids, ck.ids)
    np.testing.assert_array_equal(full.scores, ck.scores)
    np.testing.assert_array_equal(full.stats["tiles_visited"],
                                  ck.stats["tiles_visited"])
    assert (ck.stats["chunks_dispatched"] <= ck.stats["n_chunks"]).all()
    assert ck.stats["shard_chunks_dispatched"].shape == (
        len(corpus.queries), n_shards)
    np.testing.assert_allclose(ck.stats["shard_chunks_dispatched"].sum(1),
                               ck.stats["chunks_dispatched"])


def test_sharded_chunked_mesh_equals_emulation(setup):
    """The chunk while_loop under shard_map == the vmap emulation path
    (including the chunks_dispatched counters)."""
    corpus, index = setup
    p = twolevel.fast().replace(chunk_tiles=2)
    sh = shard_index(index, 1)
    emu = shard_retrieve_batched(sh, *_q(corpus), p, traversal="chunked",
                                 exchange_every=2)
    msh = shard_retrieve_batched(sh, *_q(corpus), p, traversal="chunked",
                                 exchange_every=2, mesh=make_shard_mesh(1))
    np.testing.assert_array_equal(msh.ids, emu.ids)
    np.testing.assert_array_equal(msh.scores, emu.scores)
    np.testing.assert_array_equal(msh.stats["chunks_dispatched"],
                                  emu.stats["chunks_dispatched"])


def test_sharded_chunked_rejects_unknown_traversal(setup):
    corpus, index = setup
    with pytest.raises(ValueError, match="traversal"):
        shard_retrieve_batched(shard_index(index, 2), *_q(corpus),
                               twolevel.fast(), traversal="fused")


def test_mesh_shard_count_mismatch_raises(setup):
    corpus, index = setup
    with pytest.raises(ValueError, match="shards"):
        shard_retrieve_batched(shard_index(index, 2), *_q(corpus),
                               twolevel.fast(), mesh=make_shard_mesh(1))


def test_sharded_stats_consistent(setup):
    corpus, index = setup
    res = shard_retrieve_batched(shard_index(index, 4), *_q(corpus),
                                 twolevel.fast())
    s = res.stats
    assert np.all(s["docs_survived"] <= s["docs_present"])
    assert np.all(s["docs_frozen"] <= s["docs_survived"])
    assert np.all(s["tiles_visited"] <= s["n_tiles"])
    assert s["shard_tiles_visited"].shape == (len(corpus.queries), 4)
    np.testing.assert_allclose(s["shard_tiles_visited"].sum(1),
                               s["tiles_visited"])


def test_sharded_server_matches_plain_server(setup):
    """ShardedRetrievalServer serves the same results through the queue/
    batch machinery as the single-device server."""
    from repro.serve import (Request, RetrievalServer, ServerConfig,
                             ShardedRetrievalServer)
    corpus, index = setup
    params = twolevel.fast()
    cfg = ServerConfig(max_batch=4)
    plain = RetrievalServer(index, params, cfg)
    sharded = ShardedRetrievalServer(index, params, cfg, n_shards=3)

    def reqs():
        return [Request(corpus.queries[i], corpus.q_weights_b[i],
                        corpus.q_weights_l[i]) for i in range(6)]

    for srv in (plain, sharded):
        for r in reqs():
            srv.submit(r, 0.0)
        while srv.pending:
            srv._flush()
    for a, b in zip(plain.completed, sharded.completed):
        np.testing.assert_array_equal(a.ids, b.ids)
        np.testing.assert_array_equal(a.scores, b.scores)


# -- slow lane: real 8-device collective path ---------------------------------

_MESH_PARITY_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json
    import numpy as np
    from repro.core import build_index, twolevel
    from repro.core.shard_plan import place_on_mesh, shard_index
    from repro.core.traversal import retrieve_batched
    from repro.data import make_corpus
    from repro.serve.sharded import make_shard_mesh, shard_retrieve_batched

    c = make_corpus("splade_like", n_docs=2048, n_terms=512, n_queries=12,
                    n_q_terms=5, n_rel=3, avg_doc_terms=24, seed=7)
    index = build_index(c.merged("scaled"), tile_size=256)
    q = (c.queries, c.q_weights_b, c.q_weights_l)
    sh = shard_index(index, 8)
    mesh = make_shard_mesh(8)
    out = {}

    def eq(a, b):
        return bool(np.array_equal(a.ids, b.ids)
                    and np.array_equal(a.scores, b.scores))

    # rank-safe: collective path bit-identical to single device
    p = twolevel.original(gamma=0.2)
    ref = retrieve_batched(index, *q, p)
    out["safe_docid"] = eq(shard_retrieve_batched(sh, *q, p, mesh=mesh), ref)
    pi = p.replace(schedule="impact")
    out["safe_impact"] = eq(
        shard_retrieve_batched(sh, *q, pi, mesh=mesh),
        retrieve_batched(index, *q, pi))
    # guided: mesh path == emulation path (same math, collective merge)
    pf = twolevel.fast()
    out["guided_mesh_eq_emu"] = eq(
        shard_retrieve_batched(sh, *q, pf, mesh=mesh),
        shard_retrieve_batched(sh, *q, pf))
    # threshold exchange stays exact for rank-safe configs
    out["exchange"] = eq(
        shard_retrieve_batched(sh, *q, p, mesh=mesh, exchange_every=1), ref)
    # Pallas scorer under shard_map
    out["kernel"] = eq(
        shard_retrieve_batched(sh, *q, p, mesh=mesh, use_kernel=True), ref)
    # chunked while_loop under shard_map == full impact scan per shard
    pc = pf.replace(chunk_tiles=2)
    out["chunked"] = eq(
        shard_retrieve_batched(sh, *q, pc, mesh=mesh, traversal="chunked"),
        shard_retrieve_batched(sh, *q, pc.replace(schedule="impact"),
                               mesh=mesh))
    # placed once at open: device s holds shard s of every stacked leaf
    placed = place_on_mesh(sh, mesh)
    owners = [{d.id for d in leaf.sharding.device_set}
              for leaf in placed.gather]
    rows = [s.data.shape[0] for s in placed.gather[0].addressable_shards]
    out["placed"] = (all(len(o) == 8 for o in owners) and rows == [1] * 8
                     and eq(shard_retrieve_batched(placed, *q, p, mesh=mesh),
                            ref))
    print("RESULT:" + json.dumps(out))
""")


@pytest.mark.slow
def test_sharded_mesh_parity_multi_device_subprocess():
    res = subprocess.run([sys.executable, "-c", _MESH_PARITY_SCRIPT],
                         capture_output=True, text=True, timeout=560)
    assert res.returncode == 0, res.stderr[-3000:]
    line = [ln for ln in res.stdout.splitlines()
            if ln.startswith("RESULT:")][0]
    out = json.loads(line[len("RESULT:"):])
    assert all(out.values()), out
