#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from, for one cell.

    python bench/control.py --workload <cell> --seeds 11,12,13 --seconds 20

For each seed, in one process: set up the cell at its own size, serve a
short window of its traffic through the program and compare the answers
with the reference (the program's reading), then put the reference in
the program's place, computed in bfloat16 (the precision step below the
float32 the configuration states), for the same requests and compare
that (the control's reading). Both go through the benchmark's own
verdict: the program has to come out correct and the control not.
Prints one JSON line per seed with each side's ``correct`` and its
numbers beside their limits. Needs a TPU; the benchmark's own runs
never run this.
"""
import argparse
import gc
import json
import pathlib
import sys

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path[:0] = [str(ROOT / "src"), str(BENCH)]


def judged(numbers: dict, unanswered: int) -> dict:
    """``correct`` and every number beside its limit, as a run shows them."""
    from lsrbench import check
    correct, shown = check.verdict(dict(numbers, unanswered=unanswered))
    return {"correct": correct, "checks": shown,
            "topk_agreement": numbers["topk_agreement"],
            "partial_share": numbers["partial_share"],
            "compared": numbers["compared"]}


def readings(workload: str, seed: int, seconds: float,
             overrides: dict | None = None) -> dict:
    """Program and control readings of one seed."""
    from lsrbench import cell, check, load
    from lsrbench.reference import Reference
    _, _, cfg, traffic = cell.load_spec(workload, overrides)
    due = load.due_times(traffic, seconds)
    counts = load.live_counts(traffic, seconds)
    prep = cell.prepare(cfg, traffic, counts, seed)
    win = cell.serve_window(prep, due, seconds)
    prep.sched.close(flush=False)
    corpus, params, k = prep.corpus, prep.params, prep.k
    del prep
    gc.collect()
    ref = Reference(corpus, params.alpha, params.gamma)
    queries = corpus.queries[:len(due)]
    rankings = [ref.rank(*q, k) for q in queries]
    unanswered = sum(o.error is not None for o in win.outcomes)
    program = check.check_all([None if r is None else r[:2]
                               for r in win.responses], queries, ref, k,
                              rankings)
    control = check.check_all([as_served(ref.control(*q, k), k)
                               for q in queries], queries, ref, k, rankings)
    return {"seed": seed, "requests": len(due), "compiles": win.compiles,
            "program": judged(program, unanswered),
            "control_bf16": judged(control, 0)}


def as_served(ranking, k: int):
    """A ranking's top-k as a served list, sentinel-padded to k."""
    ids, sc = ranking.top(k)
    pad = k - len(ids)
    return (list(ids) + [-1] * pad,
            [float(x) for x in sc] + [float("-inf")] * pad)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    from lsrbench import cell
    cell.start_jax()
    cell.device_info(cell.load_spec(args.workload)[1]["chips"])
    for seed in args.seeds.split(","):
        print(json.dumps(readings(args.workload, int(seed), args.seconds)),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
