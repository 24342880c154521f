"""The program's spans and scopes in a trace (``lsrbench/spans.py``):
gap labels, the idle share the host work accounts for, the gather
scope's time and roofline, on synthetic events, on recorded chip traces,
and on a CPU window of the served path. No number here is a device
metric."""
import gzip
import json
import pathlib
import shutil
import tempfile

import pytest

from lsrbench import cell, load, spans, xtrace

DATA = pathlib.Path(__file__).with_name("data")
# xtrace.reduce of tpu_trace_events.json.gz, as the benchmark computed
# it before the program's spans and scopes were read
BUSY_S, WINDOW_S, KERNEL_S = 1.3835118761999998, 1.5, 0.03676825
DEVICE_OPS = [
    ["fusion.104", 0.44291120800000006], ["fusion.103", 0.43518456400000005],
    ["fusion.105", 0.36347328100000004], ["fusion.106", 0.128072],
    ["vmap_jit_guided_score_chunk__.7", 0.03676825],
    ["fusion.100", 0.00042037300000000004], ["fusion.99", 0.000418158],
    ["fusion.101", 0.0003394360000000001], ["fusion.102", 0.000334472],
    ["fusion.17", 0.000258538]]
GATHER = ("jit(_retrieve_chunked_impl)/while/body/vmap(gather)/"
          "vmap(jit(gather_tile))/jit(_take)/gather")


def synthetic():
    """One device; ns timestamps; window 0..1000. Device 0 idles over
    [0,100), [250,600), [650,990); the executor is picking, assembling,
    parked, waiting on the device, and a collection runs."""
    return {
        "device": {"/device:TPU:0": [["fusion.1", 100, 100],
                                     ["guided_score_chunk.6", 200, 50],
                                     ["copy", 600, 50],
                                     ["fusion.1", 990, 10]]},
        "host": [["bench.window", 0, 1000], ["bench.wait", 260, 300],
                 ["bench.drain", 700, 300]],
        "spans": [["repro.pick", 250, 30, "h#2"],
                  ["repro.assemble", 280, 20, "h#2"],
                  ["repro.device_wait", 300, 200, "h#2"],
                  ["repro.park", 700, 200, "h#2"],
                  ["repro.gc", 910, 40, "h#3"],
                  ["repro.admit", 40, 10, "h#1"]],
        "op_scopes": {"fusion.1": GATHER,
                      "guided_score_chunk.6":
                          "jit(f)/while/body/vmap(score)/"
                          "jit(guided_score_chunk)/guided_score_chunk/"
                          "pallas_call",
                      "copy": "jit(f)/vmap()/gather"},
    }


def test_gaps_are_named_by_program_spans_first():
    gaps = {round(s * 1e9): lab for lab, s in spans.label_gaps(synthetic())}
    # [250,600): device_wait overlaps 200, the wait annotation 300: the
    # program's span wins; [650,990): park; [0,100): admit
    assert gaps == {350: "repro.device_wait", 340: "repro.park",
                    100: "repro.admit"}
    ev = synthetic()
    ev["spans"] = [s for s in ev["spans"] if s[0] != "repro.admit"]
    gaps = {round(s * 1e9): lab for lab, s in spans.label_gaps(ev)}
    assert gaps[100] == "unannotated"
    ev["spans"] = []
    labels = [lab for lab, _ in spans.label_gaps(ev)]
    assert labels == [lab for lab, _ in xtrace.reduce(ev)["idle_gaps"]]


def test_idle_host_share_counts_host_work_and_collections_only():
    # idle 100 + 350 + 340 = 790 ns; pick + assemble cover [250,300) and
    # gc [910,950): 90 ns of 1000; device_wait, park and admit do not count
    assert spans.idle_host_share(synthetic()) == pytest.approx(0.09)
    # any span: admit 10, pick..device_wait 250, park 200, gc 40 = 500
    # of 790
    assert spans.covered_idle_share(synthetic()) == pytest.approx(500 / 790)


def test_gather_scope_time_and_roofline():
    ev = synthetic()
    assert spans.scopes_of(GATHER) >= {"gather", "gather_tile", "_take"}
    assert "gather" not in spans.scopes_of("jit(f)/vmap()/gather")
    assert spans.scope_s(ev, "gather") == pytest.approx(110e-9)
    assert spans.scope_s(ev, "score") == pytest.approx(50e-9)
    records = [{"stats": {"postings_touched": 10, "tiles_visited": 2},
                "route": "long", "live_terms": 3}, None,
               {"stats": {"postings_touched": 99, "tiles_visited": 9},
                "route": "short", "live_terms": 2}]
    roof = {"bytes_per_posting": 12, "bytes_per_run": 8}
    assert spans.useful_bytes(records, roof) == 10 * 12 + 2 * 3 * 8
    share = spans.gather_useful_roofline(ev, records, roof, 1e12)
    assert share == pytest.approx(100.0 * 168 / 1e12 / 110e-9)
    assert spans.gather_useful_roofline(ev, [None], roof, 1e12) is None


def test_without_device_ops_there_is_no_reading():
    ev = dict(synthetic(), device={})
    assert spans.label_gaps(ev) == []
    assert spans.idle_host_share(ev) is None
    assert spans.covered_idle_share(ev) is None


def test_recorded_tpu_trace_reduces_as_before():
    """The benchmark's reduction of its recorded chip trace, pinned."""
    with gzip.open(DATA / "tpu_trace_events.json.gz", "rt") as f:
        events = json.load(f)
    red = xtrace.reduce(events)
    assert (red["busy_s"], red["window_s"], red["n_devices"]) == (
        BUSY_S, WINDOW_S, 1)
    assert red["device_ops"] == DEVICE_OPS
    assert xtrace.kernel_s(events, ("guided_score_chunk",)) == KERNEL_S


def test_served_window_on_the_cpu_reads_the_program_spans(tiny):
    spec, c, cfg, traffic = cell.load_spec(
        "splade_long_k10", dict(tiny, **{"traffic.rate_qps": 4.0}))
    due = load.due_times(traffic, 2.0)
    counts = load.live_counts(traffic, 2.0)
    prep = cell.prepare(cfg, traffic, counts, 2**33 + 5)
    tmp = tempfile.mkdtemp(prefix="bench_trace_")
    try:
        cell.serve_window(prep, due, 2.0, tmp)
        events = spans.read_xplane(xtrace.find_xplane(tmp))
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
        batches = prep.sched.stats()["batches"]
        prep.sched.close(flush=False)
    names = [s[0] for s in events["spans"]]
    assert names.count("repro.admit") == len(due)
    assert names.count("repro.deliver") == batches > 0
    executor = {s[3] for s in events["spans"] if s[0] == "repro.deliver"}
    assert len(executor) == 1
    assert {s[3] for s in events["spans"]
            if s[0] in spans.HOST_SPANS and s[0] != "repro.gc"} == executor
    assert any(h[0] == "bench.window" for h in events["host"])
    # the compiled modules name each instruction's scopes (on the CPU the
    # ops run on the host, so no device plane holds them)
    scopes = set().union(*map(spans.scopes_of, events["op_scopes"].values()))
    assert {"bounds", "gather", "score", "stats", "merge"} <= scopes


def test_recorded_tpu_trace_with_spans_and_scopes():
    """``tpu_trace_spans.json.gz``: the events that start in the first 3 s
    of a traced window of ``splade_long_k10`` on a TPU v5 lite
    (``bench/spans.py --keep-s 3``), with the program's spans and the op
    scopes of the compiled modules."""
    with gzip.open(DATA / "tpu_trace_spans.json.gz", "rt") as f:
        events = json.load(f)
    red = xtrace.reduce(events)
    assert red["n_devices"] == 1 and red["window_s"] == pytest.approx(3.0)
    top3 = [op for op, _ in red["device_ops"][:3]]
    assert all(op.startswith("fusion.") for op in top3)
    assert all("gather" in spans.scopes_of(events["op_scopes"][op])
               for op in top3)
    assert spans.scope_s(events, "gather") >= 0.85 * red["busy_s"]
    kernel = [op for op, _ in red["device_ops"] if "guided_score_chunk" in op]
    assert kernel == ["guided_score_chunk.6"]
    assert "score" in spans.scopes_of(events["op_scopes"][kernel[0]])
    kernel_s = xtrace.kernel_s(events, ("guided_score_chunk",))
    assert kernel_s <= spans.scope_s(events, "score") < 1.01 * kernel_s
    for label, seconds in spans.label_gaps(events):
        if seconds >= 1e-3:
            assert label.startswith("repro."), (label, seconds)
    idle = 1.0 - red["busy_s"] / red["window_s"]
    assert 0 < spans.idle_host_share(events) <= idle
