"""Per-query traversal telemetry -> span attributes.

The traversal executors already count the work the paper's pruning
scheme saves (``tiles_visited``, ``chunks_dispatched``, ``n_chunks``,
the doc-level skip counters) into per-query stat arrays; the scheduler
slices them per request at delivery. This module is the small adapter
that turns one request's sliced stats dict into flat scalar span
attributes, so a single exported trace shows *why* the query was slow
— its own dispatched-chunk count, not just its latency.

Imports ``core.traversal`` (which imports jax), so it is deliberately
not re-exported from ``repro.obs``'s package root: importing the
lightweight obs surface (metrics/spans/cost/export) never initializes
jax; the scheduler imports this module explicitly.
"""
from __future__ import annotations

import numpy as np

from ..core.traversal import TRACE_STAT_KEYS


def request_attributes(stats: dict, reduce=np.max) -> dict:
    """Flatten a (per-request) stats dict to scalar attributes: each
    known traversal counter reduced over the request's rows (max by
    default — the row that kept the batch's while_loop alive). Keys an
    engine doesn't produce (``chunks_dispatched`` on a full scan) are
    simply absent."""
    out = {}
    for key in TRACE_STAT_KEYS:
        v = stats.get(key)
        if v is None:
            continue
        arr = np.asarray(v, np.float64)
        if arr.size == 0 or not np.isfinite(arr).all():
            continue
        out[key] = float(reduce(arr) if arr.ndim else arr)
    return out

