"""Workload-serving runtime — the subsystem that turns the engine stack into
a query *service* (ROADMAP north-star: heavy traffic, amortised traversals).

Layout:

  compile.py    plan-tensor compiler: same-shape instances → one padded
                parameter tensor per shape bucket (pow-2 size buckets bound
                retracing)
  cache.py      plan cache (shape bucket × graph fingerprint → split) and
                compiled-executable cache — steady-state serving neither
                re-plans nor re-traces
  scheduler.py  admission queue + batch scheduler: groups by (shape bucket,
                mode, engine), plans each group with the batch-aware cost
                model, dispatches ONE vmapped engine call per group
                (aggregates and the partitioned engine included — no
                per-query fallback)
  replay.py     open-loop Poisson + closed-loop bounded-outstanding replay
                of the LDBC workload through the scheduler; p50/p95/p99
                latency, throughput, completion-rate, deadline-hit rate,
                goodput (the paper's Table 5 serving metrics, plus SLO
                accounting)
  admission.py  deadline admission control: cost-model-predicted wait +
                service vs deadline → admit / degrade (dense→sliced,
                bounded dispatch quantum) / reject
  telemetry.py  (predicted, measured) dispatch-cost ring buffer + periodic
                online θ refit — prediction error shrinks during serving
  faults.py     deterministic chaos injection (FaultPlan: seeded rates /
                explicit schedules at named points in the dispatch path and
                the WAL) + RetryPolicy (backoff retries accounted on the
                virtual clock, deadline-aware budgets, bisection quarantine,
                worker-loss degradation) — the completion story
  epochs.py     live-graph serving: EpochManager seals event-log epochs,
                materializes them incrementally, decides compaction, evicts
                exactly the cache entries whose fingerprints retired, and
                pins the scheduler to immutable snapshots (queries keep
                serving during ingestion — see docs/ingestion.md)
  testing.py    FakeDispatcher: synthetic service times on a virtual clock,
                zero JAX — the deterministic harness the SLO layer is
                tested on
"""
from .admission import (AdmissionController, AdmissionDecision,
                        AdmissionPolicy)
from .cache import ExecutableCache, PlanCache, graph_fingerprint
from .compile import PlanTensor, bucket_key, compile_plan_tensor
from .epochs import Epoch, EpochManager
from .faults import (CompileError, FaultError, FaultPlan, PoisonQueryError,
                     RetryPolicy, TornWriteError, TransientDispatchError,
                     WorkerLostError)
from .replay import ReplayReport, replay_workload
from .scheduler import BatchScheduler, GroupDispatch, ServedResult
from .telemetry import TelemetryBuffer
from .testing import FakeDispatcher

__all__ = [
    "BatchScheduler", "ServedResult", "GroupDispatch", "PlanCache",
    "ExecutableCache", "graph_fingerprint", "PlanTensor",
    "bucket_key", "compile_plan_tensor", "ReplayReport", "replay_workload",
    "AdmissionController", "AdmissionDecision", "AdmissionPolicy",
    "TelemetryBuffer", "FakeDispatcher", "Epoch", "EpochManager",
    "FaultPlan", "RetryPolicy", "FaultError", "TransientDispatchError",
    "CompileError", "WorkerLostError", "TornWriteError", "PoisonQueryError",
]
