#!/usr/bin/env python3
"""One traced window of a cell, read by the program's own spans and scopes.

    python bench/spans.py --workload <cell> --seed <n> --seconds <s> \
        [--out events.json.gz --keep-s 3]

Sets the cell up as ``bench/run.py`` does, serves one window of its
traffic under the profiler, and prints one JSON line: the window's
latencies and rate with the profiler on (set them beside an untraced
run of the same seed for the cost of tracing), the device's idle share,
how much of it the program's host work and collections account for
(``device_idle_host_share``) and how much of it any ``repro.*`` span
covers, the idle gaps named by span, the executor's host time per batch
from its counter and from its spans, time per span, device time by op
with each op's scopes, and the gather scope's device time and roofline
share (``lsrbench/spans.py``). ``--out`` keeps the events of the
window's first ``--keep-s`` seconds. Needs a TPU; the benchmark's own
runs never run this.
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import collections  # noqa: E402
import gzip  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path[:0] = [str(ROOT / "src"), str(BENCH)]


def reading(events: dict, stats: dict, records: list, cfg: dict,
            peaks: dict | None) -> dict:
    """Everything the spans and scopes of one traced window say."""
    from lsrbench import spans, xtrace
    red = xtrace.reduce(events)
    per_span = collections.defaultdict(lambda: [0, 0.0])
    for name, _, dur, _ in events["spans"]:
        per_span[name][0] += 1
        per_span[name][1] += dur * 1e-9
    host = [s for s in events["spans"] if s[0] in spans.HOST_SPANS
            and s[0] != "repro.gc"]
    n_batches = stats.get("batches") or 0
    gather_s = spans.scope_s(events, "gather")
    out = {
        "busy_s": red["busy_s"], "window_s": red["window_s"],
        "device_idle_share": 100.0 * (1.0 - red["busy_s"] / red["window_s"]),
        "device_idle_host_share": 100.0 * spans.idle_host_share(events),
        "covered_idle_share": 100.0 * (spans.covered_idle_share(events)
                                       or 0.0),
        "batch_host_ms": (stats.get("batch_host_ms") or {}).get("mean"),
        "batch_host_ms_from_spans": (sum(s[2] for s in host) * 1e-6
                                     / n_batches if n_batches else None),
        "batches": n_batches,
        "spans": {k: {"n": v[0], "s": v[1]}
                  for k, v in sorted(per_span.items())},
        "gather_s": gather_s,
        "gather_share_of_busy": 100.0 * gather_s / red["busy_s"],
        "device_ops": [[op, s, events["op_scopes"].get(op, "")]
                       for op, s in red["device_ops"]],
        "idle_gaps": spans.label_gaps(events),
        "idle_gaps_bench": red["idle_gaps"],
    }
    if peaks is not None:
        out["gather_useful_roofline"] = spans.gather_useful_roofline(
            events, records, cfg["roofline"], peaks["hbm_bytes_per_s"])
    return out


def kept(events: dict, seconds: float) -> dict:
    """The window's first ``seconds`` as a window of its own: the events
    that start in it, and the scopes of the ops among them."""
    from lsrbench import xtrace
    lo, _ = xtrace._window(events)
    hi = lo + seconds * 1e9
    device = {p: [e for e in evs if lo <= e[1] < hi]
              for p, evs in events["device"].items()}
    ops = {e[0] for evs in device.values() for e in evs}
    return {"device": device,
            "host": [["bench.window", lo, hi - lo]]
            + [e for e in events["host"]
               if e[0] != "bench.window" and lo <= e[1] < hi],
            "spans": [s for s in events["spans"] if lo <= s[1] < hi],
            "op_scopes": {op: p for op, p in events["op_scopes"].items()
                          if op in ops}}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out")
    ap.add_argument("--keep-s", type=float, default=3.0)
    args = ap.parse_args(argv)
    from lsrbench import cell, load, spans, xtrace
    cell.start_jax()
    _, cell_spec, cfg, traffic = cell.load_spec(args.workload)
    device = cell.device_info(cell_spec["chips"])
    peaks = cell.peaks_for(device["kind"])
    due = load.due_times(traffic, args.seconds)
    counts = load.live_counts(traffic, args.seconds)
    prep = cell.prepare(cfg, traffic, counts, args.seed)
    setup_s = time.perf_counter() - T_PROCESS
    tmp = tempfile.mkdtemp(prefix="bench_trace_")
    try:
        win = cell.serve_window(prep, due, args.seconds, tmp)
        events = spans.read_xplane(xtrace.find_xplane(tmp))
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    stats = prep.sched.stats()
    prep.sched.close(flush=False)
    records = [None if r is None else
               {"stats": r[2], "route": r[3], "live_terms": int(counts[i])}
               for i, r in enumerate(win.responses)]
    summary = load.summarize(win.outcomes, win.t0, args.seconds)
    if args.out:
        with gzip.open(args.out, "wt") as f:
            json.dump(kept(events, args.keep_s), f)
    print(json.dumps({"workload": args.workload, "seed": args.seed,
                      "device": device, "setup_s": setup_s,
                      "compiles_in_window": win.compiles,
                      "traced": {k: summary.get(k) for k in
                                 ("qps", "p50_ms", "p95_ms", "failed")},
                      **reading(events, stats, records, cfg, peaks)}),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
