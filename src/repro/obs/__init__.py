"""``repro.obs`` — tracing, metrics, export, and the serving cost model.

The observability subsystem: :mod:`~repro.obs.spans` (per-request
tracing with a bounded ring and a zero-cost disabled path, and
:func:`~repro.obs.spans.scope`, the program's only call into the JAX
profiler: the served path's ``repro.*`` spans — ``admit``, ``pick``,
``assemble``, ``dispatch``, ``device_wait``, ``finish``, ``deliver``,
``park`` and ``gc`` — on the profiler's timeline, next to the device's
operations),
:mod:`~repro.obs.metrics` (counters / gauges / mergeable log-bucketed
histograms with exact-rank quantiles), :mod:`~repro.obs.export`
(Prometheus text + JSON snapshots + the ``--metrics-port`` HTTP
server), and :mod:`~repro.obs.cost` (the trace-fitted chunk-count
predictor behind ``SchedulerConfig.sort_batches_by_cost``).

This package root stays jax-free on import: ``scope`` imports the
profiler when a span opens, and ``obs.trace_exec`` (which adapts
``core.traversal`` stats into span attributes) is imported explicitly
by its consumers, so tools like ``scripts/fit_cost_model.py`` can load a
model without initializing a backend.
"""
from .cost import FEATURES, CostModel, QueryFeaturizer  # noqa: F401
from .export import (MetricsServer, json_snapshot,  # noqa: F401
                     prometheus_text)
from .metrics import (Counter, Gauge, Histogram,  # noqa: F401
                      MetricsRegistry, exact_quantile)
from .spans import (GC_SPANS, NULL_SPAN, NULL_TRACER,  # noqa: F401
                    NullTracer, Span, Tracer, scope, scope_totals)
