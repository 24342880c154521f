"""Trace reduction and the peaks table."""
import gzip
import json
import pathlib

import pytest

from lsrbench import cell, xtrace

DATA = pathlib.Path(__file__).with_name("data")


def synthetic():
    """Two devices; ns timestamps; window 0..1000."""
    return {
        "device": {
            "/device:TPU:0": [["fusion.1", 100, 100],
                              ["while.2", 100, 150],        # holds the next
                              ["vmap_guided_score_chunk__.3", 150, 100],
                              ["copy", 600, 50],
                              ["guided_score_chunk_q__.1", 990, 40]],
            "/device:TPU:1": [["fusion.1", 0, 500]],
        },
        "host": [["bench.window", 0, 1000],
                 ["bench.wait", 260, 300],
                 ["bench.drain", 700, 300]],
    }


def test_busy_union_idle_and_gaps():
    red = xtrace.reduce(synthetic())
    assert red["window_s"] == pytest.approx(1000e-9)
    # device 0: [100,250) + [600,650) + [990,1000) = 210; device 1: 500
    assert red["busy_s"] == pytest.approx((210 + 500) / 2 * 1e-9)
    assert red["n_devices"] == 2
    gaps = dict((round(s * 1e9), lab) for lab, s in red["idle_gaps"])
    assert gaps == {340: "drain", 350: "wait", 100: "unannotated"}
    assert red["idle_gaps"][0][0] == "wait"          # longest first
    ops = dict(red["device_ops"])
    assert ops["fusion.1"] == pytest.approx(600e-9)
    assert "while.2" not in ops                      # a container op


def test_kernel_time_matches_names_inside_the_window():
    ev = synthetic()
    assert xtrace.kernel_s(ev, ("guided_score_chunk",)) == pytest.approx(
        110e-9)
    assert xtrace.kernel_s(ev, ("nothing",)) == 0.0


def test_without_window_mark_the_trace_extent_is_the_window():
    ev = synthetic()
    ev["host"] = [h for h in ev["host"] if h[0] != "bench.window"]
    red = xtrace.reduce(ev)
    assert red["window_s"] == pytest.approx(1030e-9)


def test_recorded_tpu_trace():
    path = DATA / "tpu_trace_events.json.gz"
    with gzip.open(path, "rt") as f:
        events = json.load(f)
    red = xtrace.reduce(events)
    assert red["n_devices"] == 1
    assert 0 < red["busy_s"] < red["window_s"]
    assert xtrace.kernel_s(events, ("guided_score_chunk",)) > 0
    assert red["device_ops"] and red["idle_gaps"]


def test_peaks_table_knows_the_v5e_and_refuses_others():
    p = cell.peaks_for("TPU v5 lite")
    assert p["hbm_bytes_per_s"] == 819e9 and p["bf16_flops_per_s"] == 197e12
    assert "TPU v5e" in cell.PEAKS["source"]
    with pytest.raises(KeyError, match="not in the peaks table"):
        cell.peaks_for("TPU v99")
