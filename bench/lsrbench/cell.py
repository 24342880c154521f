"""One run of one cell: set-up, open-loop window, drain, comparison.

Everything a cell needs is found by name: the cell in BENCHMARK.json
names a configuration (``bench/configs/<config>.json``) and a traffic
mix (``bench/traffic/<traffic>.json``); each per-layer metric is read by
``bench/metrics/<name>.py``. The program is driven through its served
path only: ``AsyncRetrievalScheduler.submit`` with an executor pool and
the Table-8 routing policy (short queries on the jnp chunked route, long
ones on the fused Pallas chunk kernel) cut to the routes the cell's
traffic takes, over the configuration's fp32 index.
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import importlib.util
import json
import os
import pathlib
import shutil
import sys
import tempfile
import time

import numpy as np

from . import check, gen, load, xtrace
from .reference import Reference

BENCH = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
DRAIN_S = 60.0           # how long answers are awaited past the window
PEAKS = json.loads((pathlib.Path(__file__).with_name("peaks.json")
                    ).read_text())


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def start_jax() -> None:
    """Keep JAX's persistent compile cache at the fixed
    ``<checkout>/.jax_cache``, every program in it, before JAX starts;
    the program's own cache helper takes the directory given here."""
    if not (ROOT / "src" / "repro").is_dir():
        raise SystemExit(f"the program is not in this checkout "
                         f"({ROOT / 'src' / 'repro'} is missing)")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    import jax
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


def device_info(chips: int = 1, require_tpu: bool = True) -> dict:
    """The devices as JAX reports them; no TPU, or fewer chips than the
    cell asks for, ends the run before anything is built."""
    import jax
    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    if require_tpu and device["platform"] != "tpu":
        raise SystemExit(f"no TPU: JAX's backend is {device['platform']}; "
                         f"nothing to measure")
    if device["count"] < chips:
        raise SystemExit(f"the cell needs {chips} chips, JAX sees "
                         f"{device['count']}")
    return device


def load_spec(workload: str, overrides: dict | None = None):
    """(benchmark, cell, config, traffic) for one workload name;
    ``overrides`` ({"section.field": value}) resize it for tests."""
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {c["name"]: c for c in spec["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; have "
                         f"{sorted(cells)}")
    cell = cells[workload]
    cfg = json.loads((BENCH / "configs" / f"{cell['config']}.json"
                      ).read_text())
    traffic = json.loads((BENCH / "traffic" / f"{cell['traffic']}.json"
                          ).read_text())
    for key, val in (overrides or {}).items():
        section, field = key.split(".")
        (traffic if section == "traffic" else cfg[section])[field] = val
    return spec, cell, cfg, traffic


def peaks_for(kind: str) -> dict:
    if kind not in PEAKS["devices"]:
        raise KeyError(f"device kind {kind!r} is not in the peaks table "
                       f"({sorted(PEAKS['devices'])})")
    return PEAKS["devices"][kind]


@contextlib.contextmanager
def count_compiles():
    """Count compilations (fresh, or loaded from the persistent cache)
    requested inside the block."""
    from jax import monitoring
    seen = []

    def on_duration(event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            seen.append(event)

    def on_event(event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            seen.append(event)
    monitoring.register_event_duration_secs_listener(on_duration)
    monitoring.register_event_listener(on_event)
    try:
        yield seen
    finally:
        monitoring.unregister_event_duration_listener(on_duration)
        monitoring.unregister_event_listener(on_event)


def annotate(name: str, on: bool):
    if not on:
        return contextlib.nullcontext()
    import jax
    return jax.profiler.TraceAnnotation("bench." + name)


@dataclasses.dataclass
class Prepared:
    """A cell after set-up: generated data, the program's warmed
    scheduler (which holds the index), and the set-up phase times."""
    corpus: object
    sched: object
    k: int
    params: object        # the pruning preset's TwoLevelParams
    phases: dict


def cell_policy(srv: dict, counts):
    """The Table-8 routing policy cut to the routes this cell's traffic
    takes (the last one kept becomes the catch-all), so warm-up builds
    only the shapes the window uses."""
    from repro.serve import table8_policy
    from repro.serve.router import RoutingPolicy
    full = table8_policy(short_max_len=srv["short_max_len"],
                         long_engine=srv["long_engine"],
                         long_traversal=srv["long_traversal"])
    taken = {full.classify(int(n)).name for n in set(counts)}
    used = [r for r in full.routes if r.name in taken]
    last = dataclasses.replace(used[-1], max_query_len=None)
    return RoutingPolicy(tuple(used[:-1]) + (last,))


def prepare(cfg: dict, traffic: dict, counts, seed: int) -> Prepared:
    """Generate the data, hand it to the program, build the
    configuration's index and warm the cell's serving shapes. Where the
    traffic names a ``content_seed``, the corpus and queries come from it
    and the seed draws the documents' ids (``gen.relabel_docs``); else
    the seed draws everything."""
    import jax
    from repro.core import build_index, twolevel
    from repro.core.align import merge_models
    from repro.core.sparse import SparseModel
    from repro.serve import AsyncRetrievalScheduler, SchedulerConfig
    if cfg["index"]["kind"] != "fp32":
        raise SystemExit(f"index kind {cfg['index']['kind']!r}: the "
                         f"benchmark builds fp32 indexes only")
    phases = {}
    t = time.perf_counter()
    content = traffic.get("content_seed")
    corpus = gen.make_corpus(cfg["corpus"], counts,
                             seed if content is None else content)
    phases["generate"] = time.perf_counter() - t
    if content is not None:
        t = time.perf_counter()
        corpus = gen.relabel_docs(corpus, seed, cfg["index"]["tile_size"])
        phases["relabel"] = time.perf_counter() - t

    t = time.perf_counter()
    c = corpus
    learned = SparseModel(c.n_docs, c.n_terms, c.indptr, c.docids, c.w_l)
    bm25 = SparseModel(c.n_docs, c.n_terms, *corpus.bm25_csr())
    merged = merge_models(learned, bm25, "scaled")
    del learned, bm25
    phases["align"] = time.perf_counter() - t

    t = time.perf_counter()
    index = build_index(merged, tile_size=cfg["index"]["tile_size"])
    del merged
    jax.block_until_ready(jax.tree_util.tree_leaves(index.gather_arrays()))
    phases["build"] = time.perf_counter() - t

    t = time.perf_counter()
    srv = cfg["serving"]
    params = getattr(twolevel, cfg["pruning"]["preset"])()
    if params.bound_mode != "list":
        raise SystemExit("the reference orders terms by list-level bounds; "
                         f"bound_mode {params.bound_mode!r} is not covered")
    sched_cfg = SchedulerConfig(max_batch=srv["max_batch"],
                                pad_terms=srv["pad_terms"], cache_size=0,
                                executors=srv["executors"])
    k = int(traffic["k"])
    sched = AsyncRetrievalScheduler(index, params, sched_cfg,
                                    routing=cell_policy(srv, counts),
                                    k_buckets=(k,))
    sched.start()                  # warms every (route x k-bucket) shape
    phases["warmup"] = time.perf_counter() - t
    gc.collect()
    return Prepared(corpus, sched, k, params, phases)


@dataclasses.dataclass
class Window:
    outcomes: list        # load.Outcome per request due in the window
    responses: list       # (ids, scores, stats, route) or None if failed
    t0: float             # window start (perf_counter)
    compiles: int         # compilations requested inside the window


def serve_window(prep: Prepared, due, seconds: float,
                 trace_dir=None) -> Window:
    """Submit every request at its due time, then wait for the answers.

    With ``trace_dir`` the profiler traces the whole window and its
    drain, under a ``bench.window`` annotation."""
    from repro.retrieval import SearchRequest
    sched, k = prep.sched, prep.k
    reqs = [SearchRequest(terms=t, weights_b=b, weights_l=l, k=k)
            for t, b, l in prep.corpus.queries[:len(due)]]
    handles, outcomes = [], []
    on = trace_dir is not None
    with count_compiles() as compiles:
        if on:
            import jax
            jax.profiler.start_trace(str(trace_dir))
        try:
            with annotate("window", on):
                t0 = time.perf_counter()
                for req, off in zip(reqs, due):
                    target = t0 + float(off)
                    wait = target - time.perf_counter()
                    if wait > 0:
                        with annotate("wait", on):
                            time.sleep(wait)
                    with annotate("submit", on):
                        o = load.Outcome(due=target,
                                         submitted=time.perf_counter())
                        try:
                            handles.append(sched.submit(req, now=target))
                        except Exception as exc:   # refused at admission
                            handles.append(None)
                            o.error = repr(exc)
                    outcomes.append(o)
                deadline = t0 + seconds + DRAIN_S
                with annotate("drain", on):
                    for h in handles:
                        if h is None:
                            continue
                        try:
                            h.result(timeout=max(0.0, deadline
                                                 - time.perf_counter()))
                        except Exception:   # judged below, per request
                            pass
        finally:
            if on:
                jax.profiler.stop_trace()
    responses = []
    for h, o in zip(handles, outcomes):
        if h is None or not h.done():
            o.error = o.error or "no answer before the drain ended"
            responses.append(None)
            continue
        try:
            r = h.result(timeout=0)
        except Exception as exc:
            o.error = repr(exc)
            responses.append(None)
            continue
        o.done = h.t_done
        stats = {n: float(np.asarray(v).ravel()[0])
                 for n, v in r.stats.items() if np.size(v)}
        responses.append((np.asarray(r.ids)[0], np.asarray(r.scores)[0],
                          stats, h.route))
    return Window(outcomes, responses, t0, len(compiles))


def load_reader(name: str):
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def applies(metric: dict, cell_name: str) -> bool:
    return "workloads" not in metric or cell_name in metric["workloads"]


def run(workload: str, seed: int, seconds: float, trace: bool,
        t_process: float, require_tpu: bool = True,
        overrides: dict | None = None) -> dict:
    """One run of one cell. Returns the result line (a dict), or raises
    SystemExit when there is no accelerator to measure."""
    import jax
    spec, cell, cfg, traffic = load_spec(workload, overrides)
    device = device_info(cell["chips"], require_tpu)
    peaks = peaks_for(device["kind"]) if require_tpu else None

    due = load.due_times(traffic, seconds)
    counts = load.live_counts(traffic, seconds)
    prep = prepare(cfg, traffic, counts, seed)
    log("setup " + " ".join(f"{k}_s={v:.3f}" for k, v in
                            prep.phases.items()))

    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    try:
        win = serve_window(prep, due, seconds, trace_dir)
        outcomes, responses, t0, compiles = (win.outcomes, win.responses,
                                             win.t0, win.compiles)
        setup_s = t0 - t_process
        events = None
        if trace:
            path = xtrace.find_xplane(trace_dir)
            events = xtrace.read_xplane(path) if path else None
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)
    stats = prep.sched.stats()
    prep.sched.close(flush=False)
    mem = jax.devices()[0].memory_stats() or {}
    device["memory_peak_bytes"] = int(mem.get("peak_bytes_in_use", 0))
    summary = load.summarize(outcomes, t0, seconds)
    log(f"window setup_s={setup_s:.3f} requests={summary['attempted']} "
        f"completed={summary['completed']} failed={summary['failed']} "
        f"compiles_in_window={compiles} "
        f"submit_late_ms_p50={summary['submit_late_ms_p50']:.3f} "
        f"submit_late_ms_max={summary['submit_late_ms_max']:.3f} "
        f"batches={stats.get('batches')} chunks_dispatched="
        f"{sum(r[2].get('chunks_dispatched', 0) for r in responses if r):.0f}")
    corpus, params = prep.corpus, prep.params
    del prep
    gc.collect()

    # the reference runs after the window, with the program's state freed
    t = time.perf_counter()
    ref = Reference(corpus, params.alpha, params.gamma)
    numbers = check.check_all(
        [None if r is None else r[:2] for r in responses], corpus.queries,
        ref, int(traffic["k"]))
    numbers["unanswered"] = summary["failed"]
    log(f"reference_s={time.perf_counter() - t:.3f} compared="
        f"{numbers['compared']} partial_share={numbers['partial_share']!r} "
        f"faults={numbers['fault_examples']}")

    ctx = {"cell": cell, "config": cfg, "traffic": traffic, "stats": stats,
           "records": [None if r is None else
                       {"stats": r[2], "route": r[3],
                        "live_terms": int(counts[i])}
                       for i, r in enumerate(responses)],
           "events": events, "peaks": peaks,
           "summary": summary}
    metrics = {}
    if trace:
        if events is not None and events["device"]:
            red = xtrace.reduce(events)
            device["busy_s"] = red["busy_s"]
            device["window_s"] = red["window_s"]
            ctx["reduction"] = red
        for m in spec["per_layer"]:
            if not applies(m, workload):
                continue
            val = load_reader(m["name"])(ctx)
            if val is not None:
                metrics[m["name"]] = {"value": float(val), "unit": m["unit"]}
    else:
        values = {"qps": summary.get("qps"), "p50_ms": summary.get("p50_ms"),
                  "p95_ms": summary.get("p95_ms"),
                  "topk_agreement": numbers["topk_agreement"],
                  "setup_s": setup_s}
        for m in spec["end_to_end"]:
            if applies(m, workload) and values.get(m["name"]) is not None:
                metrics[m["name"]] = {"value": float(values[m["name"]]),
                                      "unit": m["unit"]}
    correct, shown = check.verdict(numbers)
    shown["compiles_in_window"] = {"value": compiles, "limit": 0}
    result = {"correct": bool(correct and compiles == 0),
              "attempted": summary["attempted"],
              "failed": summary["failed"], "metrics": metrics,
              "device": device}
    if "reduction" in ctx:
        result["breakdown"] = {n: ctx["reduction"][n]
                               for n in ("device_ops", "idle_gaps")}
    result["checks"] = shown
    return result
