"""The served path on the JAX profiler's timeline: host spans
(``repro.obs.spans.scope``), the ``repro.gc`` hook, the executor's
``batch_host_ms``, and the device-side named scopes of the traversal.

Traces are collected on the CPU into a temporary directory and read back
with ``jax.profiler.ProfileData``; no number here is a device time.
"""
import gc
import glob
import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import build_index, twolevel
from repro.core.traversal import _retrieve_chunked_impl
from repro.obs.spans import GC_SPANS, scope, scope_totals
from repro.retrieval import SearchRequest
from repro.serve import AsyncRetrievalScheduler, SchedulerConfig
from repro.serve.router import single_route

BATCH = ("pick", "assemble", "dispatch", "device_wait", "finish", "deliver")


@pytest.fixture(scope="module")
def index(small_corpus):
    return build_index(small_corpus.merged("scaled"), tile_size=256)


def _profiled(tmp_path, fn):
    """Run ``fn`` under the profiler; the ``repro.*`` host events as
    (name, start_ns, duration_ns, thread) with the thread numbered by its
    line in the host plane, in start order."""
    jax.profiler.start_trace(str(tmp_path))
    try:
        out = fn()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(tmp_path, "**", "*.xplane.pb"),
                      recursive=True)
    events = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for i, line in enumerate(plane.lines):
            events += [(e.name[len("repro."):], e.start_ns, e.duration_ns,
                        (plane.name, i))
                       for e in line.events if e.name.startswith("repro.")]
    return out, sorted(events, key=lambda e: e[1])


def _requests(corpus, n):
    return [SearchRequest(terms=corpus.queries[i], k=10,
                          weights_b=corpus.q_weights_b[i],
                          weights_l=corpus.q_weights_l[i])
            for i in range(n)]


def test_every_batch_reads_its_spans_in_order_on_one_thread(
        tmp_path, small_corpus, index):
    sched = AsyncRetrievalScheduler(
        index, twolevel.original(gamma=0.2),
        SchedulerConfig(max_batch=4, max_wait_ms=5.0, cache_size=0,
                        executors=1),
        routing=single_route(traversal="chunked"), k_buckets=(10,))
    sched.start()
    reqs = _requests(small_corpus, 10)

    def serve():
        handles = [sched.submit(r) for r in reqs]
        for h in handles:
            h.result(timeout=120)
    try:
        _, events = _profiled(tmp_path, serve)
    finally:
        sched.close()
    # after close: the executor records a batch's host time once its
    # answers are out, so the last sample can trail the last answer
    stats = sched.stats()
    admits = [e for e in events if e[0] == "admit"]
    assert len(admits) == len(reqs)
    delivers = [e for e in events if e[0] == "deliver"]
    assert len(delivers) == stats["batches"] >= 3
    executor = delivers[0][3]
    assert executor != admits[0][3]        # the caller's thread admits
    batch_events = [e for e in events if e[0] in BATCH]
    assert {e[3] for e in batch_events} == {executor}
    # runs of one name collapse (two assemble and two finish spans per
    # batch; empty picks while idle); what is left is pick, then each
    # batch's six stages in order
    names = [e[0] for e in batch_events]
    runs = [n for i, n in enumerate(names) if i == 0 or names[i - 1] != n]
    assert re.fullmatch(r"(pick( assemble dispatch device_wait finish "
                        r"deliver)?\s?)+", " ".join(runs) + " ")
    assert runs.count("device_wait") == stats["batches"]
    assert stats["batch_host_ms"]["n"] == stats["batches"]
    assert stats["batch_host_ms"]["mean"] > 0


def test_gc_hook_spans_collections_and_is_installed_once(tmp_path, index):
    installs = GC_SPANS.installs
    scheds = [AsyncRetrievalScheduler(index, twolevel.fast(),
                                      SchedulerConfig(cache_size=0))
              for _ in range(2)]
    try:
        for s in scheds:
            s.start()
            s.start()                       # idempotent while running
        assert gc.callbacks.count(GC_SPANS) == 1
        assert GC_SPANS.installs == installs + 2
        _, events = _profiled(tmp_path, gc.collect)
        assert [e[0] for e in events].count("gc") >= 1
    finally:
        for s in scheds:
            s.close()
            s.close()                       # a second close removes nothing
    assert GC_SPANS.installs == installs
    assert gc.callbacks.count(GC_SPANS) == (1 if installs else 0)


def _scopes(text: str) -> set:
    """Named scopes in the op-name paths of a lowered program: every
    path component but the last (the operation), with transform wrappers
    such as ``vmap(...)`` taken off."""
    out = set()
    for path in re.findall(r'loc\("([^"]+)"', text):
        for comp in path.split("/")[:-1]:
            out.add(re.sub(r"^(\w+\()+|\)+$", "", comp))
    return out


@pytest.mark.parametrize("fused", [True, False])
def test_long_route_program_holds_the_device_scopes(index, small_corpus,
                                                    fused):
    p = twolevel.accurate()
    q = small_corpus.queries[:2]
    lowered = _retrieve_chunked_impl.lower(
        index.gather_arrays(), index.tile_max_b, index.tile_max_l,
        index.sigma_b, index.sigma_l, jnp.asarray(q, jnp.int32),
        jnp.asarray(small_corpus.q_weights_b[:2], jnp.float32),
        jnp.asarray(small_corpus.q_weights_l[:2], jnp.float32),
        jnp.float32(p.alpha), jnp.float32(p.beta), jnp.float32(p.gamma),
        jnp.float32(p.threshold_factor), k=10, kq=10, pad_len=index.pad_len,
        tile_size=index.tile_size, n_tiles=index.n_tiles,
        bound_mode=p.bound_mode, chunk_tiles=4, use_kernel=True,
        fused=fused)
    scopes = _scopes(lowered.as_text(debug_info=True))
    assert {"bounds", "gather", "score", "stats", "merge"} <= scopes


def test_scope_without_a_profile_and_its_totals():
    with scope_totals() as spent:
        with scope("assemble"):
            np.zeros(8).sum()
        with scope("assemble"), scope("inner"):
            pass
        with scope_totals() as nested:
            with scope("finish"):
                pass
    assert set(spent) == {"assemble", "inner"} and spent["assemble"] > 0
    assert set(nested) == {"finish"}
    with scope("outside"):                  # no totals, no profile
        pass


def test_importing_obs_leaves_jax_unloaded():
    code = ("import sys, repro.obs; "
            "sys.exit('jax' in sys.modules)")
    env = dict(os.environ, PYTHONPATH=os.pathsep.join(sys.path))
    assert subprocess.run([sys.executable, "-c", code], env=env,
                          timeout=120).returncode == 0


def test_gc_hook_installs_balance_under_racing_threads():
    """Installs and removals from many threads at once leave the count
    where it started and the hook in ``gc.callbacks`` at most once."""
    import threading
    installs = GC_SPANS.installs
    seen = []

    def churn():
        for _ in range(200):
            GC_SPANS.install()
            seen.append(gc.callbacks.count(GC_SPANS))
            GC_SPANS.remove()
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=churn) for _ in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(interval)
    assert set(seen) == {1} and len(seen) == 8 * 200
    assert GC_SPANS.installs == installs
    assert gc.callbacks.count(GC_SPANS) == (1 if installs else 0)
