#!/usr/bin/env bash
# One-command CI gate: tier-1 tests + heavy legs selected BY MARKER + bench
# regression gate.
#   ./scripts/ci.sh            # full gate
#   ./scripts/ci.sh --fast     # tier-1 only (every-push leg)
#
# Heavy legs (full gate only):
#   serving      the SLO serving layer (`-m serving`): deadline EDF,
#                admission control, online θ refit, and both replay modes on
#                the FakeDispatcher virtual clock (tier-1 also runs these;
#                the dedicated leg keeps the SLO surface visible in the gate)
#   obs          the query flight recorder (`-m obs`): span trees pinned on
#                the virtual clock, metrics exposition, the cost-model audit
#                replayed from trace JSONL, traced-vs-untraced bit-identity
#   ingest       live-graph serving (`-m ingest`): event-log validation,
#                incremental-vs-from-scratch materialization identity,
#                replay order-insensitivity, delta execution, epoch-pinned
#                cache metrics, and the conformance ingestion leg
#   fault        fault tolerance (`-m fault`): deterministic chaos injection,
#                retry bit-identity, deadline-aware retry budgets, poison
#                quarantine bisection, worker-loss dense fallback, and WAL
#                torn-tail crash recovery
#   docs         scripts/check_docs.py: every fenced command in README.md +
#                docs/*.md parses, the cheap ```bash run blocks execute,
#                and every file:line anchor points at a real line
#   conformance  the four-way differential matrix at CONFORMANCE_SCALE=ci
#                (full worker sweep + all ETR operators), selected with
#                `-m conformance` — tier-1 already runs it at smoke scale
#   multidevice  shard_map-native batched serving on 8 forced host devices
#                (XLA_FLAGS), bit-identity vs the vmap simulation
#   smokes       engine-vs-oracle and workload/scheduler sweeps
#   benches      serving replay + weak scaling, producing BENCH_*.json,
#                then scripts/check_bench.py diffs them against the
#                committed baselines (benchmarks/baselines/) and FAILS on
#                regression beyond the tolerance band
set -euo pipefail
cd "$(dirname "$0")/.."

export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"
export BENCH_SCALE="${BENCH_SCALE:-ci}"

echo "== tier-1: pytest (markers 'slow'/'multidevice' deselected by pytest.ini) =="
python -m pytest -x -q

if [[ "${1:-}" != "--fast" ]]; then
  echo "== serving SLO: deadlines/EDF, admission, online refit, replay (-m serving) =="
  python -m pytest -m serving -x -q
  echo "== obs: flight recorder spans, metrics, cost-model audit (-m obs) =="
  python -m pytest -m obs -x -q
  echo "== ingest: live-graph serving — event log, epochs, delta exec (-m ingest) =="
  python -m pytest -m ingest -x -q
  echo "== fault: chaos injection, retry/quarantine, worker loss, WAL recovery (-m fault) =="
  python -m pytest -m fault -x -q
  echo "== docs: fenced commands + file:line anchors (scripts/check_docs.py) =="
  python scripts/check_docs.py
  echo "== conformance: four-way differential matrix at CI scale (-m conformance) =="
  CONFORMANCE_SCALE=ci python -m pytest -m conformance -x -q
  echo "== multidevice: shard_map serving vs vmap simulation on 8 forced devices =="
  XLA_FLAGS="--xla_force_host_platform_device_count=8" \
    python -m pytest -m multidevice -x -q
  echo "== smoke: engine vs oracle (all modes/splits) =="
  python scripts/smoke_engine.py
  echo "== smoke: workload + batched scheduler =="
  python scripts/smoke_workload.py
  echo "== serving: LDBC replay through the batch scheduler (artifact: BENCH_serving.json) =="
  BENCH_ENFORCE=1 python -m benchmarks.serving
  echo "== weak scaling: measured partitioned supersteps (artifact: BENCH_weak_scaling.json) =="
  python -m benchmarks.weak_scaling
  echo "== bench gate: BENCH_*.json vs committed baselines =="
  python scripts/check_bench.py
fi

echo "CI GATE PASSED"
