"""Observability: the query flight recorder, metrics registry, and
cost-model audit pipeline.

  trace.py    hierarchical spans (query → admit → plan → compile → dispatch
              → superstep → exchange) with explicit parent handles and an
              injected clock; in-memory ring + optional JSONL sink; the
              NULL_TRACER default keeps the disabled path a no-op.  Also the
              names on a device profile: ``phase`` opens one of the
              scheduler's ``SCHED_PHASES`` host spans on the profiler's
              clock, ``scope`` one of the executors' ``DEVICE_SCOPES``
  metrics.py  counter/gauge/histogram registry with fixed log-spaced latency
              buckets, Prometheus text exposition and JSON snapshot
  audit.py    predicted-vs-measured joins recomputed from trace data alone:
              telemetry replay, θ refit drift, and the paper's "% of queries
              within X% of the optimal plan" metric

The serving runtime (serving/scheduler.py, serving/replay.py) and the
instrumented profiler (core/engine_partitioned.measure_supersteps) emit
into these; ``launch/query.py --trace-out/--metrics-out`` and
``scripts/trace_report.py`` are the operator surface, and
``jax.profiler.trace`` around serving captures the phase spans and device
scopes (docs/architecture.md, "Profiling the served path").
"""
from .metrics import (DEFAULT_LATENCY_BUCKETS_MS, Counter, Gauge, Histogram,
                      MetricsRegistry)
from .trace import (DEVICE_SCOPES, NULL_TRACER, SCHED_PHASES, NullTracer,
                    Span, StepClock, Tracer, load_jsonl, phase, scope,
                    span_trees)

__all__ = [
    "Tracer", "NullTracer", "NULL_TRACER", "Span", "StepClock",
    "load_jsonl", "span_trees", "phase", "scope", "SCHED_PHASES",
    "DEVICE_SCOPES",
    "MetricsRegistry", "Counter", "Gauge", "Histogram",
    "DEFAULT_LATENCY_BUCKETS_MS",
]
