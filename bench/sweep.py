#!/usr/bin/env python3
"""Rate sweep of one cell: one set-up, then one open-loop window per rate.

    python bench/sweep.py --workload <cell> --seed <n> --seconds <s> \
        --rates 1,2,4 [--trace-out events.json.gz]

Prints, per offered rate, the completed rate, p50/p95 latency, failures
and compilations inside the window, so the knee of a cell (the highest
rate it sustains without a growing backlog) can be read off once and
written into its traffic file. ``--trace-out`` keeps the profiler
events of one extra traced window at the first rate. Needs a TPU.
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import gzip  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path[:0] = [str(ROOT / "src"), str(BENCH)]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--trace-out")
    args = ap.parse_args(argv)
    from lsrbench import cell, load, xtrace
    cell.start_jax()
    _, spec, cfg, traffic = cell.load_spec(args.workload)
    cell.device_info(spec["chips"])
    rates = [float(r) for r in args.rates.split(",")]
    top = dict(traffic, rate_qps=max(rates))
    counts = load.live_counts(top, args.seconds)
    prep = cell.prepare(cfg, traffic, counts, args.seed)
    print(f"setup_s={time.perf_counter() - T_PROCESS:.3f} "
          + " ".join(f"{k}_s={v:.3f}" for k, v in prep.phases.items()),
          flush=True)
    for rate in rates:
        tr = dict(traffic, rate_qps=rate)
        due = load.due_times(tr, args.seconds)
        # reuse the queries drawn for the top rate, in order
        win = cell.serve_window(prep, due, args.seconds)
        s = load.summarize(win.outcomes, win.t0, args.seconds)
        print(json.dumps({"rate": rate, "compiles": win.compiles, **s}),
              flush=True)
    if args.trace_out:
        tr = dict(traffic, rate_qps=rates[0])
        due = load.due_times(tr, args.seconds)
        tmp = tempfile.mkdtemp(prefix="bench_trace_")
        try:
            cell.serve_window(prep, due, args.seconds, tmp)
            events = xtrace.read_xplane(xtrace.find_xplane(tmp))
        finally:
            shutil.rmtree(tmp, ignore_errors=True)
        with gzip.open(args.trace_out, "wt") as f:
            json.dump(events, f)
        red = xtrace.reduce(events)
        print(json.dumps({"trace": {k: red[k] for k in
                                    ("busy_s", "window_s", "n_devices")},
                          "device_ops": red["device_ops"],
                          "idle_gaps": red["idle_gaps"]}), flush=True)
    prep.sched.close(flush=False)
    return 0


if __name__ == "__main__":
    sys.exit(main())
