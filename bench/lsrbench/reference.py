"""The plain reference: exhaustive RankScore over the generated postings.

It imports nothing of the program under test and reads nothing the
program built. From the generator's postings it makes the aligned
(merged) index the way the paper's alignment does (a posting missing
from BM25 gets the ``scaled`` fill: mean BM25 weight / mean learned
weight times its learned weight). Every document that holds a query
term is scored, term by term, in float64:

    RankScore(d) = gamma * sum_t qw_b[t] w_b(t, d)
                   + (1 - gamma) * sum_t qw_l[t] w_l(t, d)

and ranked by score descending, doc id ascending.

``allowed`` gives, per document, every RankScore two-level guided
traversal may return for it (the 2GTI semantics of the paper's
sequential form): terms are ordered by their query-weighted list
maximum combined with ``alpha``, and a document accumulates them from
the highest bound down until the local level freezes it, so its score
is the sum over a leading run of that order: the full RankScore or a
partial one, never anything else.

``control`` computes the same sums in bfloat16 (weights, products and
accumulation), the precision step below the float32 arithmetic the
configuration states.
"""
from __future__ import annotations

import dataclasses

import ml_dtypes
import numpy as np

BF16 = ml_dtypes.bfloat16
TIE_RTOL = 1e-6           # term bounds this close may be ordered either way


@dataclasses.dataclass
class Ranking:
    """One query's exhaustive result: every matching document's score and
    the best ``k`` in rank order."""
    docs: np.ndarray      # [m] matching doc ids, ascending
    scores: np.ndarray    # [m] RankScore (float64)
    order: np.ndarray     # [<= k] positions in docs, best first

    @property
    def n_match(self) -> int:
        return len(self.docs)

    def top(self, k: int) -> tuple[np.ndarray, np.ndarray]:
        o = self.order[:k]
        return self.docs[o], self.scores[o]


def ranking(present: np.ndarray, dense: np.ndarray, k: int) -> Ranking:
    """Ranking from dense per-doc scores: score descending, doc id
    ascending among ties, the first ``k`` ordered."""
    docs = np.flatnonzero(present)
    scores = dense[docs].astype(np.float64)
    cand = np.arange(len(docs))
    if len(docs) > k:
        kth = -np.partition(-scores, k - 1)[k - 1]
        cand = np.flatnonzero(scores >= kth)
    order = cand[np.lexsort((docs[cand], -scores[cand]))][:k]
    return Ranking(docs, scores, order)


def list_maxima(indptr: np.ndarray, w: np.ndarray) -> np.ndarray:
    """Largest weight of each term's list (0 for an empty list)."""
    out = np.zeros(len(indptr) - 1, np.float32)
    full = np.flatnonzero(np.diff(indptr) > 0)
    if len(full):
        out[full] = np.maximum.reduceat(w, indptr[full])
    return out


class Reference:
    """Exhaustive scorer over one generated corpus."""

    def __init__(self, corpus, alpha: float, gamma: float):
        self.n_docs = corpus.n_docs
        self.indptr = corpus.indptr
        self.docids = corpus.docids
        self.alpha = alpha
        self.gamma = gamma
        # alignment: scaled fill for postings BM25 does not hold
        wb_live = corpus.w_b[corpus.in_b]
        ratio = (float(wb_live[wb_live > 0].mean())
                 / max(float(corpus.w_l[corpus.w_l > 0].mean()), 1e-12))
        self.w_b = np.where(corpus.in_b, corpus.w_b, ratio * corpus.w_l
                            ).astype(np.float32)
        self.w_l = corpus.w_l
        self.max_b = list_maxima(self.indptr, self.w_b)
        self.max_l = list_maxima(self.indptr, self.w_l)

    def postings(self, t: int):
        """(docs, w_b, w_l) of term ``t``."""
        s, e = self.indptr[t], self.indptr[t + 1]
        return self.docids[s:e], self.w_b[s:e], self.w_l[s:e]

    def rank(self, terms, qw_b, qw_l, k: int) -> Ranking:
        """Exhaustive float64 RankScore of every matching document."""
        dense = np.zeros(self.n_docs, np.float64)
        present = np.zeros(self.n_docs, bool)
        g = self.gamma
        for t, qb, ql in zip(terms, qw_b, qw_l):
            d, wb, wl = self.postings(int(t))
            # a term's docs are distinct: one fancy-indexed add per term
            dense[d] += (g * float(qb) * wb.astype(np.float64)
                         + (1.0 - g) * float(ql) * wl.astype(np.float64))
            present[d] = True
        return ranking(present, dense, k)

    def allowed(self, terms, qw_b, qw_l, ids) -> np.ndarray:
        """[len(ids), m] RankScores guided traversal may return for each
        document: the sums over every leading run of the terms in
        descending list-bound order (the full RankScore among them), and,
        where two adjacent bounds tie within ``TIE_RTOL``, the run that
        takes them in the other order."""
        terms = np.asarray(terms)
        qb = np.asarray(qw_b, np.float32)
        ql = np.asarray(qw_l, np.float32)
        a = np.float32(self.alpha)
        bound = a * (qb * self.max_b[terms]) + (np.float32(1) - a) * (
            ql * self.max_l[terms])
        order = np.argsort(bound, kind="stable")        # ascending
        ids = np.asarray(ids)
        g = self.gamma
        contrib = np.zeros((len(ids), len(terms)))       # [doc, sorted term]
        for j, i in enumerate(order):
            d, wb, wl = self.postings(int(terms[i]))
            if not len(d):
                continue
            at = np.minimum(np.searchsorted(d, ids), len(d) - 1)
            hit = d[at] == ids
            contrib[hit, j] = (
                g * float(qb[i]) * wb[at[hit]].astype(np.float64)
                + (1.0 - g) * float(ql[i]) * wl[at[hit]].astype(np.float64))
        runs = np.cumsum(contrib[:, ::-1], axis=1)[:, ::-1]   # run from j up
        b = bound[order].astype(np.float64)
        tied = np.flatnonzero(np.abs(np.diff(b))
                              <= TIE_RTOL * np.maximum(b[1:], 1e-30))
        swapped = [(runs[:, p + 2] if p + 2 < len(terms) else 0.0)
                   + contrib[:, p] for p in tied]
        return np.column_stack([runs, *swapped]) if swapped else runs

    def control(self, terms, qw_b, qw_l, k: int) -> Ranking:
        """The same ranking computed in bfloat16."""
        sb = np.zeros(self.n_docs, BF16)
        sl = np.zeros(self.n_docs, BF16)
        present = np.zeros(self.n_docs, bool)
        for t, qb, ql in zip(terms, qw_b, qw_l):
            d, wb, wl = self.postings(int(t))
            sb[d] = sb[d] + wb.astype(BF16) * BF16(qb)
            sl[d] = sl[d] + wl.astype(BF16) * BF16(ql)
            present[d] = True
        dense = BF16(self.gamma) * sb + BF16(1.0 - self.gamma) * sl
        return ranking(present, dense, k)
