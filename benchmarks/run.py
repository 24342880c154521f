"""Benchmark harness — one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--only tableX ...]

Prints ``name,us_per_call,derived`` CSV rows. After the selected
modules run, every recorded ``BENCH_*.json`` next to this file is
scanned for NaN/inf values — a non-finite number in a committed
benchmark means a lane silently failed, so the harness exits non-zero
and names the offending paths instead of shipping it.
"""
from __future__ import annotations

import argparse
import pathlib
import sys
import time

from benchmarks.common import validate_bench_files
from repro.launch.compile_cache import enable_compile_cache

MODULES = ("figure1", "table2", "table3", "table4", "figure3",
           "table6_suite", "table7_bmw", "table8_qlen", "dense_transfer",
           "bench_kernels", "sharded_scaling", "retrieval_smoke",
           "serving_bench", "quality_bench", "roofline", "million_doc")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", nargs="*", default=None)
    args = ap.parse_args()
    enable_compile_cache()
    mods = args.only or MODULES
    print("name,us_per_call,derived")

    def out(line: str) -> None:
        print(line, flush=True)

    for name in mods:
        mod = __import__(f"benchmarks.{name}", fromlist=["run"])
        t0 = time.time()
        try:
            mod.run(out)
        except Exception as e:  # noqa: BLE001
            print(f"{name}/ERROR,nan,error={type(e).__name__}: {e}",
                  file=sys.stderr)
            raise
        print(f"# {name} done in {time.time()-t0:.0f}s", file=sys.stderr)

    bad = validate_bench_files(pathlib.Path(__file__).resolve().parent.parent)
    if bad:
        for fname, paths in bad.items():
            print(f"{fname}/ERROR,nan,non_finite={';'.join(paths[:10])}",
                  file=sys.stderr)
        raise SystemExit(
            f"non-finite values in recorded benchmarks: {sorted(bad)}")


if __name__ == "__main__":
    main()
