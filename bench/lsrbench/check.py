"""The comparison that decides ``correct``.

Three numbers, each held to its own limit (``LIMITS``; the readings
they were set from are in PERF.md):

- ``score_gap``: the largest distance from a served RankScore to the
  nearest RankScore that guided traversal may return for that document
  (``Reference.allowed``: the full RankScore, or a partial one over a
  leading run of the query's terms in descending list-bound order), as
  a share of the request's best reference score. A sound run reads only
  float32 rounding here. A score too high or too low, a term skipped or
  a part of the score left out, an id that names the wrong document,
  or scoring in a lower precision reads far above it.
- ``list_faults``: requests whose served list breaks what every correct
  traversal gives: at least min(k, matching docs) distinct in-range ids
  with finite scores in descending order, then the empty-queue
  sentinels (-1, -inf). Exact: the limit is 0.
- ``unanswered``: requests due in the window that never brought an
  answer (failed, shed, or still out when the drain ended). Exact.

``topk_agreement`` (an end-to-end metric, not a limit) is the mean of
|served top-k ∩ exhaustive top-k| / |exhaustive top-k|: guided
traversal is rank-unsafe by design, so pruning harder shows there.
``partial_share`` (printed, not a limit) is the share of served entries
that are partial RankScores.
"""
from __future__ import annotations

import numpy as np

LIMITS = {"score_gap": 1e-4, "list_faults": 0, "unanswered": 0}
PARTIAL_RTOL = 1e-5       # a served score this far below the full one


def check_one(ids, scores, k: int, ranking, allowed, n_docs: int):
    """(fault or None, gap, agreement, partial entries) of one served
    list; ``allowed(ids)`` gives each id's allowed RankScores."""
    ids = np.asarray(ids).ravel()[:k]
    scores = np.asarray(scores, np.float64).ravel()[:k]
    want = min(k, ranking.n_match)
    ref_ids, ref_scores = ranking.top(want)
    top1 = float(ref_scores[0]) if want and ref_scores[0] > 0 else 1.0
    valid = ids >= 0
    nv = int(valid.sum())
    v_ids, v_sc = ids[:nv], scores[:nv]
    fault = None
    if len(ids) != k:
        fault = f"{len(ids)} entries for k={k}"
    elif nv < want:
        fault = f"{nv} results, {want} expected"
    elif not valid[:nv].all() or (ids[nv:] != -1).any() or not np.all(
            np.isneginf(scores[nv:])):
        fault = "results not followed by sentinels"
    elif (v_ids >= n_docs).any() or len(np.unique(v_ids)) != nv:
        fault = "ids out of range or repeated"
    elif not np.isfinite(v_sc).all() or (np.diff(v_sc) > 0).any():
        fault = "scores not finite and descending"
    if not nv or fault:
        return fault, 0.0, (0.0 if want else 1.0), 0
    ok = allowed(v_ids)                                   # [nv, m]
    gap = float(np.max(np.min(np.abs(ok - v_sc[:, None]), axis=1))) / top1
    partial = int(np.sum(v_sc < ok[:, 0] - PARTIAL_RTOL * top1))
    agree = (len(np.intersect1d(v_ids, ref_ids)) / want) if want else 1.0
    return fault, gap, agree, partial


def check_all(served, queries, ref, k: int, rankings=None) -> dict:
    """Compare every served list with the reference.

    ``served`` holds (ids, scores) per request, None for one that failed
    (it is counted in ``failed`` elsewhere and is not compared);
    ``rankings`` may hold the reference rankings already made."""
    faults, gaps, agree, partial, entries = [], [], [], 0, 0
    for i, (res, q) in enumerate(zip(served, queries)):
        if res is None:
            continue
        ranking = rankings[i] if rankings is not None else ref.rank(*q, k)
        f, g, a, p = check_one(res[0], res[1], k, ranking,
                               lambda ids: ref.allowed(*q, ids), ref.n_docs)
        if f is not None:
            faults.append((i, f))
        gaps.append(g)
        agree.append(a)
        partial += p
        entries += int((np.asarray(res[0]).ravel()[:k] >= 0).sum())
    return {"score_gap": max(gaps, default=0.0),
            "list_faults": len(faults),
            "fault_examples": faults[:5],
            "topk_agreement": float(np.mean(agree)) if agree else None,
            "partial_share": partial / entries if entries else 0.0,
            "compared": len(agree)}


def verdict(numbers: dict) -> tuple[bool, dict]:
    """(correct, {name: {"value", "limit"}}) against ``LIMITS``."""
    shown = {name: {"value": numbers[name], "limit": lim}
             for name, lim in LIMITS.items()}
    ok = (numbers["compared"] > 0
          and all(numbers[n] <= lim for n, lim in LIMITS.items()))
    return ok, shown
