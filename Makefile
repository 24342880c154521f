# Test lanes. `make test` is the pre-review gate: the fast lane first
# (collection regressions surface in seconds), then the slow lane
# (multi-device subprocess collectives, the 2^20-doc build).
PY      := python
PYTEST  := PYTHONPATH=src $(PY) -m pytest -q

.PHONY: test test-fast test-slow test-api test-serve test-faults \
    test-stress test-traversal \
        test-quality test-index test-obs tier1 bench-smoke

test: test-fast test-slow

# Includes tests/test_retrieval_api.py, which exercises the engine
# registry end-to-end for every registered engine name.
test-fast:
	$(PYTEST) -m "not slow"

test-slow:
	$(PYTEST) -m slow

# Seconds-scale smoke of the unified search API alone (registry coverage,
# facade parity, k-bucketing) — the quickest pre-commit signal.
test-api:
	$(PYTEST) -m "not slow" tests/test_retrieval_api.py

# Serving fast lane: the async scheduler / router / response-cache suite,
# the executor-pool/backpressure tests (minus the threaded saturation
# soaks), and the deprecated-server shim edges (the quickest signal when
# touching serve/scheduler.py, serve/executor.py, serve/router.py, or
# serve/engine.py).
test-serve:
	$(PYTEST) -m "not slow and not stress" tests/test_scheduler.py \
	    tests/test_executor.py tests/test_serve_edges.py

# Fault-tolerance fast lane: deadlines, retries + hedging, breakers +
# degraded mode, generation-safe hot swap, and the fault-injection soak
# — all on a simulated clock, so the whole suite runs in seconds (the
# quickest signal when touching serve/health.py, serve/faults.py, or
# the scheduler's fault paths).
test-faults:
	$(PYTEST) tests/test_serve_faults.py

# Multi-worker saturation soaks: executor pools under overload with
# shedding and concurrent submitters (threaded, timing-sensitive — kept
# out of the fast serve lane).
test-stress:
	$(PYTEST) -m stress

# Traversal fast lane: the chunked/full/kernel parity + early-exit suite
# (the quickest signal when touching core/plan, core/traversal, or the
# guided_score kernels).
test-traversal:
	$(PYTEST) -m "not slow" tests/test_traversal.py tests/test_kernels.py

# Relevance lane: metric properties, the eval harness (graded corpora,
# TREC round-trip, the small-k guided-degradation regression), and the
# hybrid cascade/rrf engine suite — the quickest signal when touching
# core/metrics.py, repro/eval/, or retrieval/hybrid.py.
test-quality:
	$(PYTEST) -m "not slow" tests/test_metrics.py tests/test_eval_harness.py \
	    tests/test_hybrid_engines.py

# Compressed-index lane: codec round-trips/bound-safety, q8 decode parity
# across every engine, and the streaming builder's chunked-vs-oneshot +
# kill-and-resume suite (the quickest signal when touching repro/index/,
# data/builder.py, or the q8 decode in kernels/guided_score.py). The
# 2^20-doc build runs in the slow lane (`-m slow tests/test_builder.py`).
test-index:
	$(PYTEST) -m "not slow" tests/test_index_codec.py \
	    tests/test_compressed_index.py tests/test_builder.py

# Observability lane: exact-rank quantiles + mergeable histograms, the
# span tracer (simulated clocks, ring eviction, disabled-path overhead
# guard), Prometheus/JSON export + the metrics HTTP server, the cost
# model (monotonicity, predictor-vs-realized, cost-sorted dispatch
# parity), and the BENCH-JSON non-finite guard (the quickest signal when
# touching src/repro/obs/ or benchmarks/common.py).
test-obs:
	$(PYTEST) tests/test_obs.py

# The exact tier-1 command from ROADMAP.md (everything, fail-fast).
tier1:
	$(PYTEST) -x

# Seconds-scale CI benches: the sharded scaling smoke (1-device mesh),
# the retrieval perf baseline (BENCH_retrieval.json: mrt_ms,
# tiles_visited, chunks_dispatched per method), the Poisson-load
# serving benchmark (BENCH_serving.json: QPS/MRT/P99 + cache-hit and
# routing stats per policy), the relevance grid (BENCH_quality.json:
# MRR/nDCG/recall next to MRT per method x threshold_factor x engine),
# and the compressed-index smoke (size ratio / build rate / chunked MRT
# at 64k docs; the committed BENCH_index.json is the 2^20-doc run —
# re-record with REPRO_BENCH_FULL=1 or --full) for later PRs to diff.
bench-smoke:
	PYTHONPATH=src $(PY) -m benchmarks.sharded_scaling --smoke
	PYTHONPATH=src $(PY) -m benchmarks.retrieval_smoke
	PYTHONPATH=src $(PY) -m benchmarks.serving_bench
	PYTHONPATH=src $(PY) -m benchmarks.quality_bench
	PYTHONPATH=src $(PY) -m benchmarks.million_doc --out /tmp/BENCH_index_smoke.json
