"""Open-loop load: due times, request shapes, and the latency arithmetic.

Every seed gets the same work: the same number of requests, arriving at
the same due times (the quantiles of an exponential at the traffic's
rate, in one fixed shuffled order: a Poisson stream without the luck of
the draw) with the same live-term counts in the same order. Where the
traffic names a ``content_seed``, the corpus and the queries are the same
for every seed too, and the seed draws the documents' ids
(``gen.relabel_docs``): the same work, with other answers. (Permuting the
arrivals by the seed, or drawing the corpus and queries from it, changed
the batching, and with it the latencies, far more than two runs of one
seed differ: whether a query needs two, three or four chunks of tiles
turns on the corpus it meets.)

Each request is timed from its due time to its completion. A request
that fails, is shed, rejected or expired, or is not complete when the
drain after the window ends, counts as failed and is never a latency
sample.
"""
from __future__ import annotations

import dataclasses
import math

import numpy as np


ORDER = 20260417          # the one fixed shuffle of gaps and term counts


def n_requests(traffic: dict, seconds: float) -> int:
    return max(1, int(round(traffic["rate_qps"] * seconds)))


def due_times(traffic: dict, seconds: float) -> np.ndarray:
    """Offsets (s) from the window start, ascending, all inside it."""
    n = n_requests(traffic, seconds)
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n)        # Exp(1) quantiles
    gaps = np.random.default_rng([ORDER, 1]).permutation(gaps)
    t = np.cumsum(gaps)
    return t * seconds / (t[-1] + gaps.mean())


def live_counts(traffic: dict, seconds: float) -> np.ndarray:
    """Live query terms per request: the traffic's distribution, rounded
    to whole requests (largest remainders), in the fixed shuffled order."""
    n = n_requests(traffic, seconds)
    spec = traffic["live_terms"]
    values = np.asarray(spec["values"], np.int64)
    w = np.asarray(spec.get("weights", [1] * len(values)), np.float64)
    share = w / w.sum() * n
    cnt = np.floor(share).astype(np.int64)
    rest = np.argsort(-(share - cnt), kind="stable")[:n - cnt.sum()]
    cnt[rest] += 1
    return np.random.default_rng([ORDER, 2]).permutation(
        np.repeat(values, cnt))


@dataclasses.dataclass
class Outcome:
    """What one request came to."""
    due: float                 # absolute perf_counter due time
    submitted: float           # when it was actually submitted
    done: float = math.nan     # completion time (nan: failed or not done)
    error: str | None = None   # why it failed


def exact_quantile(values, q: float) -> float:
    """Nearest-rank quantile: a latency some request actually had."""
    v = np.sort(np.asarray(values, np.float64))
    return float(v[max(0, math.ceil(q * len(v)) - 1)])


def summarize(outcomes: list, window_start: float,
              window_s: float) -> dict:
    """End-to-end numbers over all requests due in the window."""
    lat = [(o.done - o.due) * 1e3 for o in outcomes if o.error is None]
    failed = sum(o.error is not None for o in outcomes)
    out = {"attempted": len(outcomes), "failed": failed,
           "completed": len(lat)}
    if lat:
        last = max(o.done for o in outcomes if o.error is None)
        span = max(window_s, last - window_start)
        out.update(qps=len(lat) / span, p50_ms=exact_quantile(lat, 0.50),
                   p95_ms=exact_quantile(lat, 0.95), span_s=span)
    late = [(o.submitted - o.due) * 1e3 for o in outcomes]
    out["submit_late_ms_p50"] = exact_quantile(late, 0.5)
    out["submit_late_ms_max"] = float(max(late))
    return out
