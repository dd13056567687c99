#!/usr/bin/env python
"""Bench-regression gate: diff freshly produced BENCH_*.json artifacts
against the committed baselines (benchmarks/baselines/) with a per-metric
tolerance band, and exit non-zero on regression — the CI perf trajectory
lock (scripts/ci.sh runs this after the serving + weak-scaling benches).

Tolerance design: wall-clock numbers vary with the host, so the gate pins

  * STRUCTURAL metrics exactly (point-to-point exchange volumes per channel,
    dispatch counts): same seeds → same graph → same partition → same lane
    content; any drift means the executor's boundary traffic changed;
  * RATIO metrics (batched-vs-sequential throughput, weak-scaling and
    balance efficiency, completion rates) within a generous multiplicative
    band — host-speed cancels in a ratio, so a real regression (a serialized
    batch path, a broken exchange) shows as a large drop while scheduler
    jitter does not.

Refresh the baselines intentionally (never implicitly) with --refresh after
a reviewed perf change:  python scripts/check_bench.py --refresh
"""
from __future__ import annotations

import argparse
import json
import os
import re
import shutil
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BASELINE_DIR = os.path.join(REPO, "benchmarks", "baselines")

# (artifact, dotted path, kind, tolerance)
#   min_frac  fresh >= tol * baseline   (ratios/efficiencies: gate the drop)
#   max_rise  fresh <= tol * baseline   (costs: gate the rise)
#   exact     fresh == baseline         (structural invariants)
#   max_abs   fresh <= tol              (absolute, baseline-free: overhead
#                                        ratios whose acceptable value is a
#                                        constant, not a host-dependent one)
CHECKS = [
    # ---- serving: the batching win and its distributed leg.  The ratio
    # bands are wide (0.5) because the sequential denominator swings with
    # host load; benchmarks/serving.py separately enforces the ABSOLUTE >=2x
    # batched-vs-sequential floor via BENCH_ENFORCE, so the gate here only
    # has to catch collapses (a serialized batch path), not jitter.
    ("BENCH_serving.json", "throughput_ratio", "min_frac", 0.50),
    ("BENCH_serving.json", "dynamic_leg.throughput_ratio", "min_frac", 0.50),
    ("BENCH_serving.json", "sequential.completion_rate", "min_frac", 0.95),
    ("BENCH_serving.json", "replay.completion_rate", "min_frac", 0.95),
    ("BENCH_serving.json", "batched.n_dispatches", "exact", 0),
    ("BENCH_serving.json", "partitioned.throughput_vs_sequential",
     "min_frac", 0.50),
    ("BENCH_serving.json", "partitioned.n_dispatches", "exact", 0),
    ("BENCH_serving.json", "partitioned.exchange_volumes.state", "exact", 0),
    ("BENCH_serving.json", "partitioned.exchange_volumes.extremum",
     "exact", 0),
    ("BENCH_serving.json", "partitioned.exchange_volumes.etr", "exact", 0),
    ("BENCH_serving.json", "partitioned.exchange_per_superstep.state",
     "exact", 0),
    ("BENCH_serving.json", "partitioned.exchange_per_superstep.etr",
     "exact", 0),
    # ---- SLO layer: online refit, deadline admission, bounded closed loop.
    # benchmarks/serving.py separately enforces the ABSOLUTE acceptance
    # (admitted p99 <= deadline < plain p99, reject_rate > 0) via
    # BENCH_ENFORCE; the gate pins the ratios so the layer cannot silently
    # decay.  The closed-loop counters are structural (wave composition is
    # deterministic given the seeded workload) and pinned exactly.
    ("BENCH_serving.json", "slo.refit.improvement", "min_frac", 0.30),
    ("BENCH_serving.json", "slo.refit.online_tail_err", "max_rise", 2.50),
    ("BENCH_serving.json", "slo.overload.admitted_hit_rate",
     "min_frac", 0.80),
    ("BENCH_serving.json", "slo.overload.divergence", "min_frac", 0.40),
    ("BENCH_serving.json", "slo.overload.reject_rate", "max_rise", 1.30),
    ("BENCH_serving.json", "slo.closed.max_outstanding", "exact", 0),
    ("BENCH_serving.json", "slo.closed.max_batch", "exact", 0),
    ("BENCH_serving.json", "slo.closed.n_dispatches", "exact", 0),
    ("BENCH_serving.json", "slo.closed.completion_rate", "min_frac", 0.95),
    # ---- observability: the flight recorder must stay off the hot path.
    # Both are absolute gates (the acceptable ceiling is a constant): traced
    # dispatch time within 5% of untraced, and the disabled NullTracer
    # path's analytic bound within 1%.  Bit-identity of traced results is
    # asserted inside benchmarks/serving.py itself.
    ("BENCH_serving.json", "obs.traced_overhead", "max_abs", 1.05),
    ("BENCH_serving.json", "obs.null_overhead", "max_abs", 1.01),
    # ---- live-graph serving: epoch-pinned drains while ingesting.  The
    # structural counters (epochs, compactions, delta dispatches, the
    # bit-identity flag) are deterministic given the seeds and pinned
    # exactly; the latency ratio is an absolute ceiling (its acceptable
    # value is a constant — BENCH_ENFORCE inside benchmarks/serving.py
    # applies the same 3x floor).
    ("BENCH_serving.json", "ingest.latency_ratio", "max_abs", 3.0),
    ("BENCH_serving.json", "ingest.frozen_identical", "exact", 0),
    ("BENCH_serving.json", "ingest.n_epochs", "exact", 0),
    ("BENCH_serving.json", "ingest.n_compactions", "exact", 0),
    ("BENCH_serving.json", "ingest.delta_exec_dispatches", "exact", 0),
    ("BENCH_serving.json", "ingest.completion_rate", "min_frac", 0.95),
    # ---- fault tolerance: the chaos leg's completion contract is EXACT
    # (benchmarks/serving.py asserts it via BENCH_ENFORCE too — the gate
    # here keeps the counters from drifting: same seeded FaultPlan → same
    # consultations → same retry/quarantine/fallback counts).  Goodput vs
    # fault-free is a ratio band; recovery identity and the recovered WAL
    # shape are structural.
    ("BENCH_serving.json", "chaos.completion_rate", "min_frac", 1.0),
    ("BENCH_serving.json", "chaos.answers_identical", "exact", 0),
    ("BENCH_serving.json", "chaos.n_retries", "exact", 0),
    ("BENCH_serving.json", "chaos.n_quarantined", "exact", 0),
    ("BENCH_serving.json", "chaos.n_fallbacks", "exact", 0),
    ("BENCH_serving.json", "chaos.n_timeout", "exact", 0),
    ("BENCH_serving.json", "chaos.partitioned_restored", "exact", 0),
    ("BENCH_serving.json", "chaos.goodput_ratio", "min_frac", 0.50),
    ("BENCH_serving.json", "chaos.recovery.recovery_identical", "exact", 0),
    ("BENCH_serving.json", "chaos.recovery.n_recovered_epochs", "exact", 0),
    ("BENCH_serving.json", "chaos.recovery.n_open_survivors", "exact", 0),
    # ---- weak scaling: efficiency band + structural exchange per row
    ("BENCH_weak_scaling.json", "rows[*].balance_eff", "min_frac", 0.70),
    ("BENCH_weak_scaling.json", "rows[*].weak_eff", "min_frac", 0.55),
    ("BENCH_weak_scaling.json", "rows[*].edge_cut", "max_rise", 1.15),
    ("BENCH_weak_scaling.json", "rows[*].exchange_volume", "exact", 0),
    ("BENCH_weak_scaling.json", "rows[*].etr_exchange_volume", "exact", 0),
    ("BENCH_weak_scaling.json", "rows[*].exchange_per_query.state",
     "exact", 0),
    ("BENCH_weak_scaling.json", "rows[*].exchange_per_query.extremum",
     "exact", 0),
    ("BENCH_weak_scaling.json", "rows[*].exchange_per_query.etr", "exact", 0),
]

_TOKEN = re.compile(r"([A-Za-z0-9_]+)|\[(\*|\d+)\]")


def _resolve(obj, path: str):
    """Resolve a dotted path with [i]/[*] list steps; [*] fans out."""
    outs = [obj]
    for tok in _TOKEN.finditer(path):
        key, idx = tok.group(1), tok.group(2)
        nxt = []
        for o in outs:
            if key is not None:
                nxt.append(o[key])
            elif idx == "*":
                nxt.extend(o)
            else:
                nxt.append(o[int(idx)])
        outs = nxt
    return outs


def check_artifact(fresh_path: str, base_path: str, checks) -> list:
    with open(fresh_path) as f:
        fresh = json.load(f)
    with open(base_path) as f:
        base = json.load(f)
    # baselines are committed at ONE scale; a fresh artifact from another
    # BENCH_SCALE has different graphs/row counts, so every structural diff
    # would be spurious — skip loudly rather than fail on apples vs oranges
    if fresh.get("scale") != base.get("scale"):
        print(f"  [skip] scale mismatch: fresh={fresh.get('scale')!r} vs "
              f"baseline={base.get('scale')!r} — no comparable checks")
        return []
    failures = []
    for _, path, kind, tol in checks:
        try:
            f_vals = _resolve(fresh, path)
            # max_abs is baseline-free: an older committed baseline need not
            # carry the key at all
            b_vals = ([None] * len(f_vals) if kind == "max_abs"
                      else _resolve(base, path))
        except (KeyError, IndexError, TypeError) as e:
            failures.append((path, kind, f"unresolvable: {e!r}"))
            continue
        if len(f_vals) != len(b_vals):
            failures.append((path, kind,
                             f"fan-out {len(f_vals)} != {len(b_vals)}"))
            continue
        for i, (fv, bv) in enumerate(zip(f_vals, b_vals)):
            tag = path if len(f_vals) == 1 else f"{path}#{i}"
            if kind == "exact":
                ok, want = fv == bv, f"== {bv}"
            elif kind == "min_frac":
                ok, want = fv >= tol * bv, f">= {tol:g}·{bv:.4g}"
            elif kind == "max_rise":
                ok, want = fv <= tol * bv, f"<= {tol:g}·{bv:.4g}"
            elif kind == "max_abs":
                ok, want = fv <= tol, f"<= {tol:g} (absolute)"
            else:
                raise ValueError(kind)
            status = "ok  " if ok else "FAIL"
            print(f"  [{status}] {tag}: {fv:.6g} (want {want})"
                  if isinstance(fv, float) else
                  f"  [{status}] {tag}: {fv} (want {want})")
            if not ok:
                ref = f"absolute ceiling {tol:g}" if kind == "max_abs" \
                    else f"baseline {bv}"
                failures.append((tag, kind, f"{fv} vs {ref}"))
    return failures


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--fresh-dir", default=REPO,
                    help="directory holding the freshly produced BENCH_*.json")
    ap.add_argument("--baseline-dir", default=BASELINE_DIR)
    ap.add_argument("--refresh", action="store_true",
                    help="copy fresh artifacts over the committed baselines")
    args = ap.parse_args()

    artifacts = sorted({c[0] for c in CHECKS})
    if args.refresh:
        os.makedirs(args.baseline_dir, exist_ok=True)
        for name in artifacts:
            src = os.path.join(args.fresh_dir, name)
            shutil.copy(src, os.path.join(args.baseline_dir, name))
            print(f"refreshed baseline {name}")
        return 0

    failures = []
    for name in artifacts:
        fresh = os.path.join(args.fresh_dir, name)
        base = os.path.join(args.baseline_dir, name)
        if not os.path.exists(fresh):
            failures.append((name, "-", "fresh artifact missing"))
            print(f"{name}: FRESH ARTIFACT MISSING ({fresh})")
            continue
        if not os.path.exists(base):
            failures.append((name, "-", "baseline missing"))
            print(f"{name}: BASELINE MISSING ({base}) — run with --refresh")
            continue
        print(f"{name} vs {os.path.relpath(base, REPO)}:")
        failures += check_artifact(fresh, base,
                                   [c for c in CHECKS if c[0] == name])
    if failures:
        print(f"\nBENCH GATE FAILED: {len(failures)} regression(s)")
        for tag, kind, msg in failures:
            print(f"  - {tag} [{kind}]: {msg}")
        return 1
    print("\nBENCH GATE PASSED")
    return 0


if __name__ == "__main__":
    sys.exit(main())
