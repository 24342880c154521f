"""Corpus and query generator: learned-sparse postings with planted answers.

The semantics follow the synthetic presets the retrieval code is
evaluated on (``splade_like``, ``unicoil_like``): a BM25 layer of Zipf
term occurrences with geometric term frequencies, a learned layer that
re-weights every BM25 posting (log-normal noise) and adds expansion-only
postings (gamma weights) from ``expansion_rate / (1 - expansion_rate)``
Zipf draws per BM25 posting, and per query a few relevant documents
(strong learned boosts, BM25-visible on only part of the query terms)
and hard distractors (strong BM25, learned just below the relevant
band).

Unlike a draw-then-sort generator, every term's posting list is drawn
as a Bernoulli process over the doc ids (geometric gaps): each (term,
doc) pair is present independently, with the probability that Poisson
thinning of the Zipf draws gives it. Lists come out sorted and unique,
the corpus needs no global sort, and blocks of terms are drawn in
parallel threads, each from its own seeded stream, so the result is a
pure function of ``seed`` whatever the thread timing.
"""
from __future__ import annotations

import concurrent.futures
import dataclasses
import os

import numpy as np

K1, B = 0.9, 0.4          # BM25 parameters
BLOCK_DRAWS = 1 << 23     # gap draws per block of terms


@dataclasses.dataclass
class Corpus:
    """Term-major postings of the learned model (a superset of BM25's)."""
    n_docs: int
    n_terms: int
    indptr: np.ndarray    # [n_terms + 1] int64
    docids: np.ndarray    # [nnz] int32, sorted within each term
    w_l: np.ndarray       # [nnz] f32 learned weight (> 0)
    in_b: np.ndarray      # [nnz] bool: posting present in the BM25 index
    w_b: np.ndarray       # [nnz] f32 BM25 weight (0 where not in_b)
    queries: list         # [(terms int32, qw_b f32, qw_l f32)] per request

    @property
    def nnz(self) -> int:
        return int(self.docids.shape[0])

    def expansion_share(self) -> float:
        """Share of learned postings absent from the BM25 index."""
        return float(1.0 - self.in_b.mean()) if self.nnz else 0.0

    def term_of(self) -> np.ndarray:
        return np.repeat(np.arange(self.n_terms, dtype=np.int32),
                         np.diff(self.indptr))

    def bm25_csr(self):
        """(indptr, docids, weights) of the BM25 layer alone."""
        counts = np.bincount(self.term_of()[self.in_b],
                             minlength=self.n_terms)
        indptr = np.zeros(self.n_terms + 1, np.int64)
        np.cumsum(counts, out=indptr[1:])
        return indptr, self.docids[self.in_b], self.w_b[self.in_b]


def zipf_probs(n_terms: int, a: float) -> np.ndarray:
    p = 1.0 / np.arange(1, n_terms + 1, dtype=np.float64) ** a
    return p / p.sum()


def presence(cfg: dict) -> tuple[np.ndarray, np.ndarray]:
    """Per-term presence probabilities (q: BM25 layer, u: union).

    Expansion draws that land on a BM25 posting add nothing, so the
    expansion-only share of the learned postings comes out below
    ``expansion_rate``, as it does for draw-then-dedupe generation."""
    p = zipf_probs(cfg["n_terms"], cfg["zipf_a"])
    q = -np.expm1(-cfg["avg_doc_terms"] * p)
    rate = cfg["expansion_rate"]
    e = -np.expm1(-rate / (1.0 - rate) * q.sum() * p)
    u = 1.0 - (1.0 - q) * (1.0 - e)
    return q, u


def _term_blocks(draws: np.ndarray) -> list:
    """Contiguous term ranges of about BLOCK_DRAWS gap draws each."""
    edges = np.searchsorted(np.cumsum(draws),
                            np.arange(BLOCK_DRAWS, draws.sum(), BLOCK_DRAWS))
    bounds = np.unique(np.concatenate([[0], edges, [len(draws)]]))
    return list(zip(bounds[:-1], bounds[1:]))


def _draw_block(seed_seq, n_docs, t0, t1, draws, log1mu, p_bm25, plants):
    """Pass 1 for one block of terms: sorted doc ids with the block's
    planted pairs merged in, BM25 labels and term frequencies, the raw
    random numbers the learned weights are made from, and the block's
    share of the BM25 document lengths."""
    rng = np.random.default_rng(seed_seq)
    cnt = draws[t0:t1]
    term_of = np.repeat(np.arange(t0, t1, dtype=np.int32), cnt)
    u01 = rng.random(int(cnt.sum()), dtype=np.float32)
    gap = np.floor(np.log1p(-u01) / log1mu[term_of].astype(np.float32))
    gap = np.minimum(gap, np.float32(n_docs)) + np.float32(1.0)
    c = np.cumsum(gap, dtype=np.float64)
    before = np.concatenate([[0.0], c[np.cumsum(cnt)[:-1] - 1]])
    pos = c - np.repeat(before, cnt) - 1.0
    keep = pos < n_docs
    term_of = term_of[keep]
    docids = pos[keep].astype(np.int32)
    n = len(docids)
    b = {"term_of": term_of, "docids": docids,
         "in_b": rng.random(n, dtype=np.float32) < p_bm25[term_of],
         "tf": (1 + rng.geometric(0.55, n)).astype(np.float32),
         "z": rng.standard_normal(n, dtype=np.float32),
         "g": rng.standard_gamma(1.5, n, dtype=np.float32),
         "boost": np.zeros(n, np.float32)}
    pkeys, boost, add_tf = plants
    keys = term_of.astype(np.int64) * n_docs + docids
    at = np.searchsorted(keys, pkeys)
    hit = at < n
    hit[hit] = keys[at[hit]] == pkeys[hit]
    h = at[hit]
    b["boost"][h] = boost[hit]
    b["in_b"][h] |= add_tf[hit] > 0
    b["tf"][h] = np.where(add_tf[hit] > 0, add_tf[hit], b["tf"][h])
    new = ~hit
    if new.any():
        nk = pkeys[new]
        ins = {"term_of": (nk // n_docs).astype(np.int32),
               "docids": (nk % n_docs).astype(np.int32),
               "in_b": add_tf[new] > 0, "tf": add_tf[new],
               "z": np.zeros(len(nk), np.float32),
               "g": np.ones(len(nk), np.float32), "boost": boost[new]}
        b = {k: np.insert(v, at[new], ins[k]) for k, v in b.items()}
    d_b = b["docids"][b["in_b"]]
    b["len_part"] = np.bincount(d_b, weights=b["tf"][b["in_b"]],
                                minlength=n_docs)
    b["df"] = np.bincount(b["term_of"][b["in_b"]] - t0,
                          minlength=t1 - t0).astype(np.float32)
    b["count"] = np.bincount(b["term_of"] - t0, minlength=t1 - t0)
    return b


def _weights(b, idf, doc_len, avg_len, noise):
    """Pass 2 for one block: BM25 weights from the global document
    lengths, and learned weights (planted boost, else re-weighted BM25,
    else expansion weight)."""
    in_b, tf = b["in_b"], b["tf"]
    d_b = b["docids"][in_b]
    tf_b = tf[in_b]
    denom = tf_b + K1 * (1.0 - B + B * doc_len[d_b] / avg_len)
    w_b = np.zeros(len(in_b), np.float32)
    w_b[in_b] = idf[b["term_of"][in_b]] * tf_b * (K1 + 1.0) / denom
    w_l = np.where(in_b, w_b * np.exp(np.float32(noise) * b["z"]),
                   np.float32(0.6) * b["g"])
    w_l = np.where(b["boost"] > 0, b["boost"], w_l)
    return w_b, np.maximum(w_l, np.float32(1e-6)).astype(np.float32)


def query_terms(rng, n: int, n_terms: int) -> np.ndarray:
    """``n`` distinct terms from the frequency band of ranks
    ``[n_terms / 64, n_terms / 2)``, one from each of ``n`` equal strata
    of that band: each term is uniform over the band, as a plain draw
    would make it, but every query holds the same mix of common and rare
    terms. How many common terms a query holds sets how many tile chunks
    its traversal needs, so a plain draw lets the share of expensive
    queries, and with it the latency, vary from seed to seed."""
    lo, hi = n_terms // 64, n_terms // 2
    edges = lo + (np.arange(n + 1) * (hi - lo)) // n
    return rng.integers(edges[:-1], edges[1:]).astype(np.int32)


def plant(rng, cfg: dict, live_counts, n_docs: int, n_terms: int):
    """Queries with planted relevant docs and hard distractors.

    Returns the queries and the planted (term, doc) pairs with their
    learned boost and BM25 term frequency (0 = not BM25-visible)."""
    n_rel, n_dis = cfg["n_rel"], cfg["n_distract"]
    per_q = n_rel + n_dis
    nq = len(live_counts)
    terms = [query_terms(rng, int(n), n_terms) for n in live_counts]
    pools = np.stack([rng.choice(n_docs, size=per_q, replace=False)
                      for _ in range(nq)]).astype(np.int64)
    lens = np.asarray(live_counts, np.int64)
    # one row per (query, pool doc, query term); groups are (query, doc)
    group_len = np.repeat(lens, per_q)
    q_of = np.repeat(np.repeat(np.arange(nq), per_q), group_len)
    slot = np.repeat(np.tile(np.arange(per_q), nq), group_len)
    within = np.arange(len(q_of)) - np.repeat(np.cumsum(group_len)
                                              - group_len, group_len)
    flat_terms = np.concatenate(terms)
    t = flat_terms[(np.cumsum(lens) - lens)[q_of] + within].astype(np.int64)
    d = pools[q_of, slot]
    rel = slot < n_rel
    n = len(t)
    # relevant: learned boost on every term, BM25-visible on a random
    # subset with one term forced visible (the lexical core)
    visible = rng.random(n) > cfg["rel_on_expansion"]
    visible |= within == rng.integers(0, lens[q_of])
    boost = np.where(rel, rng.gamma(4.0, 1.0, n) + 4.0,
                     rng.gamma(3.0, 0.8, n) + 1.5).astype(np.float32)
    # distractors: BM25 term frequency 2..6 on ~70% of the query terms
    add_tf = np.where(rel, np.where(visible, rng.integers(1, 4, n), 0),
                      np.where(rng.random(n) < 0.7, rng.integers(2, 7, n),
                               0)).astype(np.float32)
    queries = [(tt, np.ones(len(tt), np.float32),
                (1.0 + rng.gamma(2.0, 0.5, len(tt))).astype(np.float32))
               for tt in terms]
    return queries, t * n_docs + d, boost, add_tf


def make_corpus(cfg: dict, live_counts, seed: int,
                threads: int | None = None) -> Corpus:
    """Generate a corpus and one query per entry of ``live_counts``."""
    n_docs, n_terms = int(cfg["n_docs"]), int(cfg["n_terms"])
    s_plant, s_blocks = np.random.SeedSequence(int(seed)).spawn(2)
    q, u = presence(cfg)
    m = n_docs * u
    draws = np.minimum(np.ceil(m + 6.0 * np.sqrt(m) + 8.0),
                       n_docs).astype(np.int64)
    log1mu = np.log1p(-np.minimum(u, 1.0 - 1e-7))
    p_bm25 = (q / u).astype(np.float32)

    queries, pkeys, boost, add_tf = plant(np.random.default_rng(s_plant),
                                          cfg, live_counts, n_docs, n_terms)
    pkeys, first = np.unique(pkeys, return_index=True)   # first plant wins
    boost, add_tf = boost[first], add_tf[first]
    blocks = _term_blocks(draws)
    cut = np.searchsorted(pkeys, np.array([t0 for t0, _ in blocks]
                                          + [n_terms], np.int64) * n_docs)
    jobs = [(seq, t0, t1, tuple(a[cut[i]:cut[i + 1]]
                                for a in (pkeys, boost, add_tf)))
            for i, (seq, (t0, t1)) in enumerate(
                zip(s_blocks.spawn(len(blocks)), blocks))]
    workers = threads or min(8, os.cpu_count() or 1)
    with concurrent.futures.ThreadPoolExecutor(workers) as pool:
        parts = list(pool.map(lambda j: _draw_block(
            j[0], n_docs, j[1], j[2], draws, log1mu, p_bm25, j[3]), jobs))
        doc_len = np.maximum(sum(b.pop("len_part") for b in parts),
                             1.0).astype(np.float32)
        df = np.concatenate([b["df"] for b in parts])
        idf = np.log(1.0 + (n_docs - df + 0.5) / (df + 0.5)
                     ).astype(np.float32)
        avg_len = float(doc_len.mean())
        wts = list(pool.map(lambda b: _weights(
            b, idf, doc_len, avg_len, cfg["weight_noise"]), parts))
    indptr = np.zeros(n_terms + 1, np.int64)
    np.cumsum(np.concatenate([b["count"] for b in parts]), out=indptr[1:])
    return Corpus(n_docs=n_docs, n_terms=n_terms, indptr=indptr,
                  docids=np.concatenate([b["docids"] for b in parts]),
                  w_l=np.concatenate([w[1] for w in wts]),
                  in_b=np.concatenate([b["in_b"] for b in parts]),
                  w_b=np.concatenate([w[0] for w in wts]),
                  queries=queries)


def relabel_docs(c: Corpus, seed: int, tile: int,
                 threads: int | None = None) -> Corpus:
    """The same corpus under the document ids a seed draws: the full
    tiles of ``tile`` documents in a random order, and the documents
    within each tile in a random order (a ragged last tile keeps its
    place). Every tile holds the same documents as before, so every
    query meets the same tile bounds, visits the same tiles and does the
    same work; only the ids it has to return differ."""
    rng = np.random.default_rng(np.random.SeedSequence(int(seed)))
    n_full = c.n_docs // tile
    new_of = np.arange(c.n_docs, dtype=np.int32)
    new_of[:n_full * tile] = (
        rng.permutation(n_full).astype(np.int32)[:, None] * tile
        + rng.permuted(np.broadcast_to(np.arange(tile, dtype=np.int32),
                                       (n_full, tile)), axis=1)).ravel()
    docids = np.empty_like(c.docids)
    w_l, w_b, in_b = (np.empty_like(a) for a in (c.w_l, c.w_b, c.in_b))

    def part(span):
        lo, hi = int(c.indptr[span[0]]), int(c.indptr[span[1]])
        d = new_of[c.docids[lo:hi]]
        keys = np.repeat(np.arange(span[0], span[1], dtype=np.int64),
                         np.diff(c.indptr[span[0]:span[1] + 1])) * c.n_docs
        order = np.argsort(keys + d, kind="stable")
        docids[lo:hi] = d[order]
        for dst, src in ((w_l, c.w_l), (w_b, c.w_b), (in_b, c.in_b)):
            dst[lo:hi] = src[lo:hi][order]
    spans = _term_blocks(np.diff(c.indptr))
    with concurrent.futures.ThreadPoolExecutor(
            threads or min(8, os.cpu_count() or 1)) as pool:
        list(pool.map(part, spans))
    return dataclasses.replace(c, docids=docids, w_l=w_l, w_b=w_b,
                               in_b=in_b)
