"""The control comes out as not correct: the reference in the program's
place, computed in bfloat16, one precision step below what the
configuration states, fails the benchmark's own verdict on three seeds,
by more than three times the ``score_gap`` limit, while the program
passes it."""
import json
import pathlib

import pytest

import control
from lsrbench import check

CELLS = [w["name"] for w in json.loads(
    (pathlib.Path(__file__).resolve().parents[2] / "BENCHMARK.json")
    .read_text())["workloads"]]


@pytest.mark.parametrize("workload", CELLS)
def test_control_fails_and_program_passes(workload, tiny):
    limit = check.LIMITS["score_gap"]
    for seed in (101, 2**32 + 5, 9_000_000_007):
        r = control.readings(workload, seed, 3.0, overrides=tiny)
        assert r["program"]["correct"] is True, r["program"]
        assert r["control_bf16"]["correct"] is False
        gap = r["control_bf16"]["checks"]["score_gap"]["value"]
        assert gap > 3 * limit, r["control_bf16"]
