#!/usr/bin/env python3
"""Smoke run of the served retrieval path on a TPU.

    python chip_smoke.py              # one chip: index, kernels, engines, serving
    python chip_smoke.py --chips 4    # sharded engine on a 2x2 mesh vs one device

One process drives everything (a chip belongs to one process). Each phase
prints one ``phase=<name> wall_s=<seconds>`` line, timed on the host clock
around work that ends in ``block_until_ready``; first calls include their
compilation. Any failed check raises, so the script exits non-zero and never
prints the final line. On a backend other than ``tpu`` it exits non-zero
before building anything. The last line of a passing run is

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

The corpus is ``splade_like`` at 2^20 documents (about an eighth of MS MARCO
passage) over the 30,522-entry BERT WordPiece vocabulary, generated from the
seed. Rank-safe configurations are checked against the exhaustive host
oracle (``core/oracle.py``) and the served ids against direct
``Retriever.search`` calls. This is a smoke run, not a benchmark: its times
include compilation and host-side index building.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import functools
import gc
import json
import pathlib
import sys
import time

import numpy as np

ROOT = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))


@dataclasses.dataclass(frozen=True)
class Config:
    n_docs: int = 2**20          # ~1/8 of MS MARCO passage (8.8M passages)
    n_terms: int = 30522         # BERT WordPiece vocabulary (SPLADE, uniCOIL)
    avg_doc_terms: int = 16      # ~105 postings/doc after splade_like expansion
    tile_size: int = 1024
    chunk_tiles: int = 8
    n_queries: int = 16
    query_terms: int = 32        # long (expanded) query width
    short_terms: int = 4         # short route: queries of <= 4 live terms
    n_requests: int = 64
    max_batch: int = 8
    executors: int = 2
    exchange_every: int = 32     # sharded phase: tiles between theta exchanges
    seed: int = 0


# Mean per-query top-k overlap bands. Q8: rank-safe search on the q8 index
# against the exhaustive fp32 ranking (the band tests/test_compressed_index.py
# pins for the quantized impacts). GUIDED: 2GTI-Fast (rank-unsafe by design)
# against the exhaustive RankScore ranking R_gamma at k=10, where its
# BM25-guided pruning drops part of the top-k on this 92%-expansion corpus
# (the paper's small-k loss; 0.76 measured on a v5e). At k=1000 it keeps
# nearly all of it, so the guided check runs at k=10 only.
Q8_BAND = 0.95
GUIDED_BAND = 0.7
GUIDED_K = 10
KS = (10, 1000)
SHARDED_K = 10     # one depth: every sharded config is its own compile
ENGINES = (("batched", "chunked"), ("kernel", "chunked_fused"))


@contextlib.contextmanager
def phase(name: str):
    """Print one line with the phase's wall time when its body completes."""
    t0 = time.perf_counter()
    yield
    print(f"phase={name} wall_s={time.perf_counter() - t0:.3f}", flush=True)


def _ready(tree):
    import jax
    for leaf in jax.tree_util.tree_leaves(tree):
        if hasattr(leaf, "block_until_ready"):
            leaf.block_until_ready()
    return tree


def bytes_in_use(device) -> int | None:
    stats = device.memory_stats()
    return None if not stats else int(stats["bytes_in_use"])


@contextlib.contextmanager
def count_compiles():
    """Count XLA backend compilations inside the block."""
    from jax import monitoring
    seen = []

    def listen(event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            seen.append(duration)
    monitoring.register_event_duration_secs_listener(listen)
    try:
        yield seen
    finally:
        monitoring.unregister_event_duration_listener(listen)


# -- phases -----------------------------------------------------------------

def device_phase(require_tpu: bool = True) -> dict:
    import jax
    with phase("device"):
        devs = jax.devices()
        dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
               "count": len(devs)}
        print(f"  device platform={dev['platform']} kind={dev['kind']} "
              f"count={dev['count']}", flush=True)
    if require_tpu and dev["platform"] != "tpu":
        raise SystemExit(f"chip_smoke: no TPU (backend is "
                         f"{dev['platform']}); nothing to smoke")
    return dev


def index_phase(cfg: Config, compressed: bool = True):
    """Generate the corpus and build the fp32 BII (and its q8 form)."""
    import jax

    from repro.core import build_index
    from repro.data import make_corpus
    from repro.index import compress_index
    dev = jax.devices()[0]
    with phase("index"):
        corpus = make_corpus("splade_like", n_docs=cfg.n_docs,
                             n_terms=cfg.n_terms, n_queries=cfg.n_queries,
                             n_q_terms=cfg.query_terms,
                             avg_doc_terms=cfg.avg_doc_terms, seed=cfg.seed)
        merged = corpus.merged("scaled")
        b0 = bytes_in_use(dev)
        fp32 = _ready(build_index(merged, tile_size=cfg.tile_size))
        b1 = bytes_in_use(dev)
        q8 = (_ready(compress_index(merged, tile_size=cfg.tile_size))
              if compressed else None)
        b2 = bytes_in_use(dev)
        fp32_b = sum(a.nbytes for a in jax.tree_util.tree_leaves(
            (fp32.gather_arrays(), fp32.tile_max_b, fp32.tile_max_l,
             fp32.sigma_b, fp32.sigma_l)))
        meta_b = (fp32.tile_ptr.nbytes + fp32.tile_max_b.nbytes
                  + fp32.tile_max_l.nbytes)
        print(f"  index docs={cfg.n_docs} terms={cfg.n_terms} "
              f"postings={merged.nnz} postings_per_doc="
              f"{merged.nnz / cfg.n_docs:.1f} tile_size={fp32.tile_size} "
              f"n_tiles={fp32.n_tiles} pad_len={fp32.pad_len}", flush=True)
        print(f"  index fp32 array_bytes={fp32_b} tile_metadata_bytes="
              f"{meta_b} device_bytes_in_use_delta="
              f"{None if b0 is None else b1 - b0}", flush=True)
        if q8 is not None:
            print(f"  index q8 array_bytes={q8.nbytes()['total']} "
                  f"device_bytes_in_use_delta="
                  f"{None if b0 is None else b2 - b1} "
                  f"device_bytes_in_use={b2}", flush=True)
    return corpus, merged, fp32, q8


def kernels_phase(cfg: Config, corpus, fp32, q8, native: bool = True):
    """Each guided_score kernel at the served widths: compiled natively
    (``tpu_custom_call`` in the compiled program) and equal to the jnp
    reference on gathered index rows."""
    import jax
    import jax.numpy as jnp

    from repro.core.index import gather_tile
    from repro.index import gather_tile_q, gather_tile_q_raw
    from repro.kernels import ref
    from repro.kernels.guided_score import (guided_score_chunk,
                                            guided_score_chunk_q,
                                            guided_score_tile,
                                            guided_score_tile_q)
    with phase("kernels"):
        rng = np.random.default_rng(cfg.seed)
        qt = jnp.asarray(corpus.queries[0])
        qwb = jnp.asarray(corpus.q_weights_b[0])
        qwl = jnp.asarray(corpus.q_weights_l[0])
        nq, c = qt.shape[0], min(cfg.chunk_tiles, fp32.n_tiles)
        tiles = jnp.arange(c, dtype=jnp.int32)
        ess = jnp.asarray(rng.random((c, nq)) < 0.5, jnp.float32)
        pbeta = jnp.asarray(np.cumsum(rng.random((c, nq)), axis=1),
                            jnp.float32)
        skip = jnp.asarray(np.arange(c) % 3 == 2, jnp.int32)
        scal = tuple(jnp.float32(v) for v in (2.0, 1.0, 0.3, 0.05))
        ts, pl_ = fp32.tile_size, fp32.pad_len
        gt = q8.gather_arrays()
        offs, wb, wl = jax.vmap(lambda t: gather_tile(
            *fp32.gather_arrays(), qt, t, qwb, qwl, pad_len=pl_,
            tile_size=ts))(tiles)
        raw = jax.vmap(lambda t: gather_tile_q_raw(gt, qt, t, pad_len=pl_)
                       )(tiles)
        dec = jax.vmap(lambda t: gather_tile_q(gt, qt, t, qwb, qwl,
                                               pad_len=pl_, tile_size=ts)
                       )(tiles)
        calls = {
            "guided_score_tile": (
                functools.partial(guided_score_tile, tile_size=ts),
                (offs[0], wb[0], wl[0], ess[0], pbeta[0], *scal)),
            "guided_score_chunk": (
                functools.partial(guided_score_chunk, tile_size=ts),
                (offs, wb, wl, ess, pbeta, skip, *scal)),
            "guided_score_tile_q": (
                functools.partial(guided_score_tile_q, tile_size=ts,
                                  pad_len=pl_),
                (*(a[0] for a in raw), qwb, qwl, ess[0], pbeta[0], *scal)),
            "guided_score_chunk_q": (
                functools.partial(guided_score_chunk_q, tile_size=ts,
                                  pad_len=pl_),
                (*raw, qwb, qwl, ess, pbeta, skip, *scal)),
        }
        outs = {}
        for name, (fn, args) in calls.items():
            compiled = jax.jit(fn).lower(*args).compile()
            lowered = "tpu_custom_call" in compiled.as_text()
            if lowered != native:
                raise AssertionError(f"{name}: tpu_custom_call in compiled "
                                     f"program is {lowered}, expected "
                                     f"{native}")
            outs[name] = np.asarray(_ready(compiled(*args)))
            print(f"  kernel {name} nq={nq} pad_len={pl_} tile_size={ts} "
                  f"chunk={c} native={lowered}", flush=True)
        for t in range(c):
            want = np.asarray(ref.guided_score_tile_ref(
                offs[t], wb[t], wl[t], ess[t], pbeta[t], *scal,
                tile_size=ts))
            want_q = np.asarray(ref.guided_score_tile_ref(
                *(a[t] for a in dec), ess[t], pbeta[t], *scal,
                tile_size=ts))
            live = not int(skip[t])
            np.testing.assert_allclose(outs["guided_score_chunk"][t],
                                       want if live else 0.0,
                                       rtol=1e-4, atol=1e-4)
            np.testing.assert_allclose(outs["guided_score_chunk_q"][t],
                                       want_q if live else 0.0,
                                       rtol=1e-4, atol=1e-4)
            if t == 0:
                np.testing.assert_allclose(outs["guided_score_tile"], want,
                                           rtol=1e-4, atol=1e-4)
                np.testing.assert_allclose(outs["guided_score_tile_q"],
                                           want_q, rtol=1e-4, atol=1e-4)
        print("  kernels agree with kernels/ref.py on gathered rows",
              flush=True)


class Oracle:
    """Exhaustive host scores and rankings (``core.oracle``) per query,
    cached: every engine and index is checked against the same lists."""

    def __init__(self, merged, corpus):
        self.merged, self.corpus, self._cache = merged, corpus, {}

    def _query(self, qi: int):
        c = self.corpus
        return (self.merged, c.queries[qi], c.q_weights_b[qi],
                c.q_weights_l[qi])

    def scores(self, qi: int, x: float) -> np.ndarray:
        from repro.core.oracle import score_all_merged
        key = ("scores", qi, x)
        if key not in self._cache:
            self._cache[key] = score_all_merged(*self._query(qi), x)
        return self._cache[key]

    def ranked(self, qi: int, x: float, k: int):
        from repro.core.oracle import ranked_list
        key = ("ranked", qi, x, k)
        if key not in self._cache:
            self._cache[key] = ranked_list(*self._query(qi), x, k)
        return self._cache[key]


def check_rank_safe(ids, scores, oracle: Oracle, x: float, k: int) -> int:
    """Ids equal the oracle's top-k except where oracle scores tie; scores
    agree to fp32 accumulation. Returns the number of tie swaps."""
    swaps = 0
    for qi in range(len(ids)):
        full = oracle.scores(qi, x)
        o_ids, o_sc = oracle.ranked(qi, x, k)
        n = int((o_sc > 0).sum())            # docs the query matches
        e_ids = np.asarray(ids[qi])
        if not (e_ids[n:] == -1).all():
            raise AssertionError(f"query {qi}: ids past the {n} matching "
                                 f"docs: {e_ids[n:][:5]}")
        e_ids = e_ids[:n]
        diff = e_ids != o_ids[:n]
        tol = 1e-5 * np.maximum(1.0, np.abs(o_sc[:n]))
        if diff.any():
            gap = np.abs(full[e_ids[diff]] - o_sc[:n][diff])
            if (gap > tol[diff]).any() or len(set(e_ids)) != n:
                raise AssertionError(
                    f"query {qi}: ids differ from the oracle beyond ties at "
                    f"ranks {np.flatnonzero(diff)[:10]}")
            swaps += int(diff.sum())
        np.testing.assert_allclose(np.asarray(scores[qi])[:n], o_sc[:n],
                                   rtol=1e-4, atol=1e-4)
    return swaps


def overlap(ids, oracle: Oracle, x: float, k: int) -> float:
    """Mean per-query |top-k ∩ oracle top-k| / |oracle top-k (matching)|."""
    vals = []
    for qi in range(len(ids)):
        o_ids, o_sc = oracle.ranked(qi, x, k)
        want = set(o_ids[o_sc > 0].tolist())
        got = set(np.asarray(ids[qi]).tolist()) - {-1}
        vals.append(len(want & got) / max(1, len(want)))
    return float(np.mean(vals))


def engines_phase(cfg: Config, corpus, merged, indexes: dict) -> None:
    """Both engines on both index kinds: rank-safe at k=10 and k=1000
    against the oracle, guided at k=10 within its band."""
    from repro.core import twolevel
    from repro.retrieval import Retriever
    oracle = Oracle(merged, corpus)
    safe, fast = twolevel.original(), twolevel.fast()
    q = dict(terms=corpus.queries, weights_b=corpus.q_weights_b,
             weights_l=corpus.q_weights_l)
    with phase("engines"):
        for kind, index in indexes.items():
            for engine, traversal in ENGINES:
                for name, params, ks in (("safe", safe, KS),
                                         ("fast", fast, (GUIDED_K,))):
                    retr = Retriever.open(index, params, engine,
                                          traversal=traversal)
                    for k in ks:
                        t0 = time.perf_counter()
                        resp = _ready(retr.search(**q, k=k))
                        dt = time.perf_counter() - t0
                        if name == "safe" and kind == "fp32":
                            res = check_rank_safe(resp.ids, resp.scores,
                                                  oracle, safe.gamma, k)
                            verdict = f"oracle_equal tie_swaps={res}"
                        else:
                            x = params.gamma
                            ov = overlap(resp.ids, oracle, x, k)
                            band = Q8_BAND if name == "safe" else GUIDED_BAND
                            if ov < band:
                                raise AssertionError(
                                    f"{kind}/{engine}/{name} k={k}: top-k "
                                    f"overlap {ov:.4f} below band {band}")
                            verdict = f"overlap={ov:.4f} band={band}"
                        print(f"  engine index={kind} engine={engine} "
                              f"traversal={traversal} params={name} k={k} "
                              f"wall_s={dt:.3f} {verdict}", flush=True)


def _requests(cfg: Config, corpus) -> list:
    """Alternate short (``short_terms``) and long (full-width) queries,
    cycling k over the three serving buckets."""
    from repro.retrieval import SearchRequest
    out = []
    for i in range(cfg.n_requests):
        qi = i % len(corpus.queries)
        n = cfg.short_terms if i % 2 == 0 else cfg.query_terms
        out.append(SearchRequest(terms=corpus.queries[qi, :n],
                                 weights_b=corpus.q_weights_b[qi, :n],
                                 weights_l=corpus.q_weights_l[qi, :n],
                                 k=(10, 100, 1000)[(i // 2) % 3]))
    return out


def served_phase(cfg: Config, corpus, index) -> None:
    """The async scheduler with an executor pool and the Table-8 policy
    (long queries on the fused kernel): every handle resolves, nothing
    fails or compiles after warmup, ids equal direct searches."""
    from repro.core import twolevel
    from repro.retrieval import Retriever
    from repro.serve import (AsyncRetrievalScheduler, SchedulerConfig,
                             query_length, table8_policy)
    params = twolevel.fast()
    policy = table8_policy(short_max_len=cfg.short_terms,
                           long_engine="kernel",
                           long_traversal="chunked_fused")
    sched_cfg = SchedulerConfig(max_batch=cfg.max_batch,
                                pad_terms=cfg.query_terms, cache_size=0,
                                executors=cfg.executors)
    reqs = _requests(cfg, corpus)
    with phase("served"):
        sched = AsyncRetrievalScheduler(index, params, sched_cfg,
                                        routing=policy)
        t0 = time.perf_counter()
        sched.start()                      # warms the (route x k) grid
        warm_s = time.perf_counter() - t0
        with count_compiles() as compiles:
            t0 = time.perf_counter()
            handles = [sched.submit(r) for r in reqs]
            served = [h.result(timeout=600) for h in handles]
            window_s = time.perf_counter() - t0
        stats = sched.stats()
        sched.close()
        bad = {key: stats[key] for key in ("failed", "shed", "rejected",
                                           "expired") if stats[key]}
        if bad or stats["completed"] != len(reqs):
            raise AssertionError(f"served run lost requests: {bad}, "
                                 f"completed={stats['completed']}")
        if compiles:
            raise AssertionError(f"{len(compiles)} compilations inside the "
                                 f"served window (warmup missed a shape)")
        print(f"  served requests={len(reqs)} executors={cfg.executors} "
              f"warmup_s={warm_s:.3f} window_s={window_s:.3f} "
              f"compiles_after_warmup={len(compiles)} "
              f"routes={stats['requests_by_route']} failed=0 shed=0 "
              f"rejected=0 expired=0", flush=True)

        # direct Retriever.search per (route, k) group, in batches of the
        # served [max_batch, width] shape so the warm programs are reused
        groups: dict = {}
        for i, r in enumerate(reqs):
            rt = policy.classify(query_length(r.weights_b, r.weights_l))
            groups.setdefault((rt.name, r.k), []).append(i)
        for (rname, k), rows in sorted(groups.items()):
            rt = policy.by_name(rname)
            width = rt.pad_terms or cfg.query_terms
            retr = Retriever.open(index, params, rt.engine, **rt.opts())
            for s in range(0, len(rows), cfg.max_batch):
                part = rows[s:s + cfg.max_batch]
                t = np.zeros((cfg.max_batch, width), np.int32)
                wb = np.zeros((cfg.max_batch, width), np.float32)
                wl = np.zeros((cfg.max_batch, width), np.float32)
                for j, i in enumerate(part):
                    n = len(reqs[i].terms)
                    t[j, :n] = reqs[i].terms
                    wb[j, :n] = reqs[i].weights_b
                    wl[j, :n] = reqs[i].weights_l
                direct = retr.search(terms=t, weights_b=wb, weights_l=wl,
                                     k=k)
                for j, i in enumerate(part):
                    if not np.array_equal(served[i].ids[0], direct.ids[j]):
                        raise AssertionError(
                            f"request {i} ({rname}, k={k}): served ids "
                            f"differ from direct Retriever.search")
        print(f"  served ids equal direct Retriever.search for all "
              f"{len(reqs)} requests", flush=True)


def sharded_phase(cfg: Config, corpus, index, n_shards: int) -> None:
    """The sharded engine on an ``n_shards``-device mesh against the
    one-device batched engine: rank-safe ids bit-identical, each device
    holding its own shard."""
    from repro.core import shard_index, twolevel
    from repro.retrieval import Retriever
    from repro.serve import make_shard_mesh
    params = twolevel.original()
    q = dict(terms=corpus.queries, weights_b=corpus.q_weights_b,
             weights_l=corpus.q_weights_l)
    with phase("sharded"):
        refs = {}
        for t in ("full", "chunked"):
            retr = Retriever.open(index, params, "batched", traversal=t)
            refs[t] = retr.search(**q, k=SHARDED_K)
        mesh = make_shard_mesh(n_shards)
        devs = list(mesh.devices.flat)
        gc.collect()
        before = [bytes_in_use(d) for d in devs]
        sharded = Retriever.open(shard_index(index, n_shards), params,
                                 "sharded", mesh=mesh).engine.sharded
        _ready(sharded.gather)
        gc.collect()
        after = [bytes_in_use(d) for d in devs]
        pieces = sharded.gather[0].addressable_shards
        owners = sorted(p.device.id for p in pieces)
        if len(owners) != n_shards or any(p.data.shape[0] != 1
                                          for p in pieces):
            raise AssertionError(f"shard leaves not one per device: "
                                 f"{[p.data.shape for p in pieces]}")
        deltas = [None if a is None or b is None else b - a
                  for a, b in zip(before, after)]
        print(f"  sharded n_shards={n_shards} devices={owners} "
              f"per_device_shard_bytes_in_use_delta={deltas} "
              f"bytes_in_use={after}", flush=True)
        for t in ("full", "chunked"):
            for ex in (0, cfg.exchange_every):
                retr = Retriever.open(sharded, params, "sharded", mesh=mesh,
                                      traversal=t, exchange_every=ex)
                t0 = time.perf_counter()
                got = _ready(retr.search(**q, k=SHARDED_K))
                dt = time.perf_counter() - t0
                if not np.array_equal(got.ids, refs[t].ids):
                    raise AssertionError(
                        f"sharded traversal={t} exchange_every={ex}: ids "
                        f"differ from the one-device engine")
                dmax = float(np.max(np.abs(np.where(
                    np.isfinite(refs[t].scores),
                    got.scores - refs[t].scores, 0.0))))
                print(f"  sharded traversal={t} exchange_every={ex} "
                      f"k={SHARDED_K} wall_s={dt:.3f} ids_bit_identical=True "
                      f"max_score_diff={dmax}", flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the sharded phase on a 2x2 mesh")
    args = ap.parse_args(argv)
    from repro.launch.compile_cache import enable_compile_cache
    dev = device_phase()
    if dev["count"] < args.chips:
        raise SystemExit(f"chip_smoke: --chips {args.chips} needs "
                         f"{args.chips} devices, have {dev['count']}")
    print(f"  compile cache: {enable_compile_cache()}", flush=True)
    cfg = Config()
    if args.chips == 4:
        # placement and bit-identity do not depend on corpus size; a
        # quarter of the docs keeps the host build short
        cfg = dataclasses.replace(cfg, n_docs=cfg.n_docs // 4)
        corpus, _, index, _ = index_phase(cfg, compressed=False)
        sharded_phase(cfg, corpus, index, n_shards=4)
    else:
        corpus, merged, fp32, q8 = index_phase(cfg)
        kernels_phase(cfg, corpus, fp32, q8)
        engines_phase(cfg, corpus, merged, {"fp32": fp32, "q8": q8})
        served_phase(cfg, corpus, fp32)
    print(json.dumps({"ok": True, "device": dev}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
