#!/usr/bin/env python3
"""Benchmark of the served learned-sparse retrieval path, one cell per run.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Generates the cell's corpus and queries from the seed, builds the index
kind its configuration names, warms the serving shapes, then offers the
traffic mix open loop for ``--seconds`` and waits up to a minute for the
last answers. Every answer is compared with the exhaustive reference.
``--trace 0`` reports the end-to-end metrics, ``--trace 1`` the
per-layer ones (from a profiler trace of the same window). The last
stdout line is one JSON object; the numbers compared, each with its
limit, are the last stderr lines and the result's last key. Without a
TPU, or with fewer chips than the cell asks for, it exits non-zero and
prints no result.
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path[:0] = [str(ROOT / "src"), str(BENCH)]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    from lsrbench import cell
    cell.start_jax()
    result = cell.run(args.workload, args.seed, args.seconds,
                      bool(args.trace), T_PROCESS)
    for name, c in result["checks"].items():
        print(f"check {name} value={c['value']!r} limit={c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
