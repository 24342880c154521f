"""Every cell's machinery end to end at a tiny size on the CPU:
generation, build, scheduler, window, comparison and the result line.
No number from these runs is a device metric."""
import json
import os
import pathlib
import shutil
import subprocess
import sys
import time

import pytest

from lsrbench import cell

BENCH = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in SPEC["workloads"]]


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("workload", CELLS)
def test_cell_runs_end_to_end(workload, trace, tiny):
    r = cell.run(workload, 2**35 + 17, 3.0, bool(trace), time.perf_counter(),
                 require_tpu=False, overrides=tiny)
    assert r["correct"] is True, r["checks"]
    assert r["attempted"] == 6 and r["failed"] == 0
    assert list(r)[-1] == "checks"
    assert set(r["checks"]) == {"score_gap", "list_faults", "unanswered",
                                "compiles_in_window"}
    assert r["device"]["platform"] == "cpu" and r["device"]["count"] >= 1
    want = {m["name"] for m in SPEC["per_layer" if trace else "end_to_end"]
            if workload in m.get("workloads", [workload])}
    if trace:
        # no device plane on the CPU: the trace-derived metrics stay out
        want -= {m["name"] for m in SPEC["per_layer"]
                 if m["source"] == "device_trace"}
    assert set(r["metrics"]) == want
    for m in r["metrics"].values():
        assert m["value"] > 0 and m["unit"]
    json.dumps(r)


def _run_cli(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELLS[0], "--seed",
         "3000000000", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_command_refuses_without_a_tpu():
    p = _run_cli(ROOT)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert not p.stdout.strip()


def test_command_refuses_without_the_program(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run_cli(tmp_path)
    assert p.returncode != 0
    assert not p.stdout.strip()
