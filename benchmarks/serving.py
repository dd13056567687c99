"""Serving benchmark (paper Table 5 analogue): the LDBC Q1–Q8 workload
through the batch-scheduler runtime vs the sequential per-query loop.

Three measurements, one JSON artifact (``BENCH_serving.json``):

  sequential   GraniteServer.run_workload — per-query latencies, drain
               throughput (the pre-serving baseline);
  batched      BatchScheduler drain — one vmapped call per shape group,
               drain throughput (the ≥2× acceptance number);
  open-loop    Poisson replay through the scheduler at a rate the sequential
               loop cannot sustain — p50/p95/p99 latency, throughput,
               completion-rate-within-budget; plus the same arrival schedule
               simulated against the sequential service times, showing what
               batching buys under load;
  partitioned  the same workload through the DISTRIBUTED engine's batched
               path (one partitioned traversal sweep per shape group), with
               the per-channel point-to-point exchange volumes the cost
               model's θ_net/θ_net_etr terms are fitted on — the numbers
               that keep the accuracy claim checkable.  (Correctness of the
               shard_map multi-device dispatch is pinned by the
               ``multidevice`` pytest leg; this bench reports the resolved
               device count it ran with.)
  slo          the SLO layer: online θ refit vs a static-θ baseline on the
               same dispatch trace (predicted-vs-measured error), an
               overload sweep (plain open loop vs deadline admission — the
               plain p99 diverges past the deadline, admission holds its
               admitted p99 inside it and reports reject/degrade counts and
               goodput), and a bounded closed-loop replay with per-query
               sampled deadlines.  BENCH_ENFORCE requires ≥80% of admitted
               queries inside their deadline (p99 ≤ 1.3× deadline — wall-
               clock slack; the exact 100% property is pinned on the virtual
               clock in tests/test_serving_slo.py), plain p99 > deadline,
               and a non-zero reject rate at 3× capacity; check_bench pins
               the rates/ratios.
  obs          flight-recorder overhead: the same warm drain with tracing +
               metrics attached vs the NullTracer default, compared on the
               MEASURED dispatch time (the timed region the telemetry and
               SLO layers consume — span construction happens outside it and
               must not leak in), plus an analytic bound on the disabled
               NullTracer path from its measured per-call cost.  Writes the
               traced run's span stream to ``BENCH_serving_trace.jsonl`` (the
               CI artifact scripts/trace_report.py renders) and asserts the
               traced results bit-identical to the untraced ones.
               check_bench pins traced_overhead ≤ 1.05 and null_overhead
               ≤ 1.01 as absolute (baseline-free) gates;
  ingest       live-graph serving: a slice of the graph's edges streams
               back in through the event log while the same workload drains
               after every epoch advance — latency-while-ingesting ratio vs
               the frozen drain, delta-executable dispatch count, cache
               invalidations at compaction, and bit-identity of the final
               epoch vs a from-scratch build.  BENCH_ENFORCE requires the
               ratio <= 3x and a non-zero delta dispatch count; check_bench
               pins the structural counters.
  chaos        fault-tolerant serving under a seeded FaultPlan: the workload
               drains three times on the partitioned engine with 10%
               transient dispatch faults, one injected worker loss (dense
               fallback → down window → probe restore), and one poison
               query — completion must be 100% (answer or structured
               quarantine, never an unhandled exception) and every answered
               query bit-identical to the fault-free reference; plus a
               crash/recover sub-leg (WAL write torn mid-line, recovery
               replays to the exact pre-crash epoch fingerprint).
               BENCH_ENFORCE requires completion_rate == 1.0,
               answers_identical, recovery_identical, and non-zero
               retry/quarantine counts; check_bench pins the counters and a
               goodput floor vs fault-free.

Workload and arrivals are seeded → reproducible run-to-run; wall-clock
numbers vary with the host, ratios are the stable signal.  Compile time is
excluded (warm passes), as the paper excludes load time.
BENCH_ENFORCE=1 exits non-zero when batched drain throughput is under 2×
sequential (the ci.sh gate).
"""
from __future__ import annotations

import json
import os
import sys

import numpy as np

from repro.graphdata.ldbc import LdbcParams, generate_ldbc, graph_name
from repro.graphdata.queries import make_workload
from repro.launch.query import GraniteServer
from repro.serving import (AdmissionPolicy, BatchScheduler, PlanCache,
                           TelemetryBuffer, replay_workload)
from repro.serving.replay import poisson_arrivals
from repro.launch.compile_cache import enable_compile_cache

from .common import SCALE, emit

SEED = 33
N_PER_TEMPLATE = {"ci": 8, "full": 50}[SCALE]
N_PERSONS = {"ci": 150, "full": 1000}[SCALE]
BUDGET_S = 600.0


def sequential_replay_sim(arrivals: np.ndarray, service_s: np.ndarray) -> dict:
    """FIFO simulation of the same open-loop arrivals against sequential
    per-query service times (no batching): the baseline's latency under
    load, from measured per-query costs."""
    t, lat = 0.0, []
    for arr, svc in zip(arrivals, service_s):
        t = max(t, float(arr)) + float(svc)
        lat.append(t - float(arr))
    lat_ms = np.asarray(lat) * 1e3
    return dict(
        latency_ms_p50=float(np.percentile(lat_ms, 50)),
        latency_ms_p95=float(np.percentile(lat_ms, 95)),
        latency_ms_p99=float(np.percentile(lat_ms, 99)),
        completion_rate=float(np.mean(lat_ms <= BUDGET_S * 1e3)),
        throughput_qps=len(lat) / max(t, 1e-12),
    )


def partitioned_leg(g, wl, seq_drain_s: float, n_workers: int = 4) -> dict:
    """Batched serving on the distributed engine + its exchange volumes
    (per channel, via the executor's canonical
    ``engine_partitioned.query_exchange_volumes``).

    The LDBC templates are plain counts, so a small same-shape MIN batch is
    appended to exercise (and report) the extremum channel — all three
    point-to-point channels show up in the artifact the bench gate pins."""
    from repro.core import engine_partitioned as EP
    from repro.core.engine_partitioned import query_exchange_volumes
    from repro.graphdata.queries import to_minmax

    wl_mm = [to_minmax(inst, g) for inst in
             make_workload(g, templates=("Q2",),
                           n_per_template=N_PER_TEMPLATE, seed=SEED + 1)]
    sched = BatchScheduler(g, engine="partitioned", n_workers=n_workers,
                           use_planner=True, budget_s=BUDGET_S)
    # two flushes so the vs-sequential ratio compares like with like: the
    # plain workload (what seq_drain_s measured) drains first, the MIN batch
    # separately (it exists to exercise the extremum channel, not the ratio)
    res = sched.run(wl, warm=True)
    drain_plain_s = sum(d.service_s for d in sched.last_dispatches)
    n_disp = len(sched.last_dispatches)
    res += sched.run(wl_mm, warm=True)
    drain_mm_s = sum(d.service_s for d in sched.last_dispatches)
    n_disp += len(sched.last_dispatches)
    assert all(r.ok for r in res)
    wl_all = list(wl) + wl_mm
    _, arrays, _ = EP.partition_for(g, n_workers)
    xchg = dict(state=0, extremum=0, etr=0)
    for inst in wl_all:
        for k, v in query_exchange_volumes(inst.qry, arrays).items():
            xchg[k] += v
    return dict(
        n_workers=n_workers,
        n_devices=sched.n_devices,
        n_queries=len(wl_all),
        drain_s=drain_plain_s + drain_mm_s,
        throughput_qps=len(wl_all) / max(drain_plain_s + drain_mm_s, 1e-12),
        throughput_vs_sequential=seq_drain_s / max(drain_plain_s, 1e-12),
        n_dispatches=n_disp,
        exchange_volumes=xchg,
        exchange_per_superstep=dict(
            state=arrays.exchange_volume(),
            etr=arrays.etr_exchange_volume(),
        ),
    )


def slo_leg(g, wl, exec_cache, bat_drain_s: float, bat_tput: float,
            n_disp: int) -> dict:
    """The SLO serving experiment: online θ refit, deadline admission under
    overload, and bounded closed-loop replay.

    Three measurements (all on warm executables — the shared exec cache —
    so compile time never contaminates a virtual-clock latency):

      refit     the same dispatch trace recorded twice, once with the online
                θ refit and once as a static-θ baseline: the refit must
                shrink the tail predicted-vs-measured error (the paper's
                cost-model accuracy claim as a LIVE property);
      overload  open-loop replay at rates beyond batched capacity, with and
                without deadline admission: the plain queue's p99 diverges
                past the deadline while admission holds its ADMITTED p99
                inside it, trading rejects for goodput;
      closed    bounded-outstanding replay with per-query sampled deadlines:
                backlog (max dispatch batch) bounded by the slot count.

    Every knob self-scales from this run's measured batched cost per query,
    so the leg is meaningful on any host speed; check_bench pins the
    resulting rates/ratios against the committed baselines."""
    n = len(wl)
    c = bat_drain_s / n                       # measured batched s/query
    # a query can never finish faster than its own group's dispatch, so the
    # deadline scales from the measured PER-DISPATCH cost: ~3 dispatch times
    # is hittable when admission keeps waves short, and far below the
    # open-loop backlog at 3x capacity
    d_disp = bat_drain_s / max(n_disp, 1)
    deadline = 6.0 * d_disp
    refit_kw = dict(refit_every=8, min_samples=8, blend=0.7)

    def mk(telemetry=None, admission=None, planner_from=None):
        s = BatchScheduler(g, use_planner=True, budget_s=BUDGET_S,
                           plan_cache=PlanCache(), exec_cache=exec_cache,
                           telemetry=telemetry, admission=admission)
        if planner_from is not None:
            s._planner.coeffs.update(planner_from._planner.coeffs)
        return s

    # ---- online refit vs static θ on the same trace
    tb_online = TelemetryBuffer(**refit_kw)
    tb_static = TelemetryBuffer(refit=False)
    cal = mk(telemetry=tb_online)
    static = mk(telemetry=tb_static)
    for _ in range(4):
        cal.run(wl, warm=True)
        static.run(wl, warm=True)
    on_stats = tb_online.error_stats()
    off_stats = tb_static.error_stats()
    refit = dict(
        n_dispatches=on_stats["n"],
        n_refits=on_stats["n_refits"],
        online_tail_err=on_stats["tail_mean_abs_rel_err"],
        static_tail_err=off_stats["tail_mean_abs_rel_err"],
        improvement=off_stats["tail_mean_abs_rel_err"]
        / max(on_stats["tail_mean_abs_rel_err"], 1e-9),
    )

    # ---- overload sweep: plain open loop vs deadline admission, same trace.
    # The workload repeats 3x so the open-loop backlog has room to diverge
    # well past the deadline (all shapes stay cached — no new compiles).
    # Headroom 0.25 bounds each admitted wave to ~1 predicted dispatch:
    # per-dispatch timings at the ~1ms scale carry up to ~2x measurement
    # noise, and a query can queue one full wave before it is even
    # submitted, so the structural margin has to absorb both.
    wl_ov = list(wl) * 3
    policy = AdmissionPolicy(headroom=0.25,
                             allow_engine_downgrade=False)

    def admitted_p99(rep) -> float:
        lat = rep.latencies_ms[[i for i, s in enumerate(rep.statuses)
                                if s == "done"]]
        return float(np.percentile(lat, 99)) if lat.size else 0.0

    sweep = []
    for mult in (1.5, 3.0):
        rate = mult * bat_tput
        plain = replay_workload(mk(), wl_ov, rate_qps=rate, seed=SEED,
                                warm=True, deadline_s=deadline)
        slo_s = mk(telemetry=TelemetryBuffer(**refit_kw), admission=policy,
                   planner_from=cal)           # start from the refitted θ
        adm = replay_workload(slo_s, wl_ov, rate_qps=rate, seed=SEED,
                              warm=True, deadline_s=deadline)
        sweep.append(dict(
            rate_mult=mult, rate_qps=rate,
            plain_hit_rate=plain.deadline_hit_rate,
            plain_p99_ms=plain.latency_ms_p99,
            admitted_hit_rate=(
                float(np.mean(adm.latencies_ms[
                    [i for i, s in enumerate(adm.statuses) if s == "done"]]
                    <= deadline * 1e3)) if adm.n_completed else 0.0),
            admitted_p99_ms=admitted_p99(adm),
            deadline_hit_rate=adm.deadline_hit_rate,
            reject_rate=adm.reject_rate,
            n_degraded=adm.n_degraded,
            goodput_qps=adm.goodput_qps,
            plain_goodput_qps=plain.goodput_qps,
        ))
    top = sweep[-1]
    overload = dict(
        deadline_ms=deadline * 1e3,
        rate_qps=top["rate_qps"],
        admitted_hit_rate=top["admitted_hit_rate"],
        admitted_p99_ms=top["admitted_p99_ms"],
        plain_p99_ms=top["plain_p99_ms"],
        divergence=top["plain_p99_ms"] / max(top["admitted_p99_ms"], 1e-9),
        reject_rate=top["reject_rate"],
        goodput_qps=top["goodput_qps"],
        plain_goodput_qps=top["plain_goodput_qps"],
    )

    # ---- bounded closed loop with per-query sampled deadlines
    closed_rep = replay_workload(mk(), wl, mode="closed", max_outstanding=8,
                                 seed=SEED, warm=True,
                                 deadline_s=(4.0 * c, 12.0 * c))
    closed = dict(
        max_outstanding=closed_rep.max_outstanding,
        max_batch=closed_rep.max_batch,
        n_dispatches=closed_rep.n_dispatches,
        completion_rate=closed_rep.completion_rate,
        deadline_hit_rate=closed_rep.deadline_hit_rate,
        latency_ms_p99=closed_rep.latency_ms_p99,
    )
    return dict(deadline_ms=deadline * 1e3, refit=refit, sweep=sweep,
                overload=overload, closed=closed)


def obs_leg(g, wl, exec_cache,
            trace_path: str = "BENCH_serving_trace.jsonl") -> dict:
    """Flight-recorder overhead leg + the trace artifact.

    Overhead is compared on the MEASURED dispatch time: that is the timed
    region everything downstream trusts (telemetry rows, SLO admission, the
    cost-model audit), and span/metric bookkeeping happens strictly outside
    it — so the traced ratio gates instrumentation leaking INTO the hot
    path, not the cost of recording itself.  At ~1 ms dispatch scale on a
    shared single-core box the measurement needs four noise controls:

    * the comparison runs on an 8× replication of the workload — same
      shape groups, 8× batches — so each timed region is tens of ms and
      the fixed cache-rewarm cost after any bookkeeping amortises away;
    * within a drain, the drain is deterministic so each repeat dispatches
      the same unit sequence and the PER-DISPATCH minimum across repeats
      (GC quiesced) filters pauses landing inside one repeat's timed region;
    * the first dispatch of a flush is excluded: it absorbs the cross-flush
      cache boundary (for a traced run, the previous flush's deferred span
      emission — outside every timed region, but it still evicts the caches
      the next JAX call re-warms), which the per-dispatch min cannot filter
      because it recurs in every repeat;
    * plain and traced drains alternate in ROUNDS and the gate compares
      best-round vs best-round (min-vs-min, the standard noise-immune
      statistic) — host-noise bursts only ever inflate a round, while a
      real hot-path leak sits in every round including the best.

    The trace artifact + bit-identity check run on the ORIGINAL workload
    with the JSONL sink attached, keeping the uploaded artifact one
    drain's spans rather than the whole measurement matrix.  The
    NullTracer number is an analytic bound: its measured per-call no-op
    cost scaled by the instrumentation call count of one drain (there is
    no un-instrumented build left to diff against)."""
    import gc
    import time

    from repro.obs import MetricsRegistry, Tracer
    from repro.obs.trace import NULL_TRACER

    rounds, repeats = 5, 3
    wl_big = list(wl) * 8

    def drain(workload, tracer=None, metrics=None):
        sched = BatchScheduler(g, use_planner=True, budget_s=BUDGET_S,
                               plan_cache=PlanCache(), exec_cache=exec_cache,
                               tracer=tracer, metrics=metrics)
        res = sched.run(workload, warm=True)    # results + warm plan cache
        best = None
        gc.collect()
        gc.disable()
        try:
            for _ in range(repeats):
                sched.run(workload, warm=True)
                times = [d.service_s for d in sched.last_dispatches]
                best = (times if best is None
                        else [min(a, b) for a, b in zip(best, times)])
        finally:
            gc.enable()
        steady = best[1:] if len(best) > 1 else best
        return res, sum(steady), sched

    t_plains, t_traceds = [], []
    for _ in range(rounds):
        _, tp, _ = drain(wl_big)
        _, tt, _ = drain(wl_big, tracer=Tracer(), metrics=MetricsRegistry())
        t_plains.append(tp)
        t_traceds.append(tt)
    t_plain = min(t_plains)
    t_traced = min(t_traceds)
    ratio = t_traced / max(t_plain, 1e-12)

    # artifact drain on the original workload: the uploaded trace JSONL +
    # the traced-vs-untraced bit-identity assertion
    res_plain, _, _ = drain(wl)
    tracer = Tracer(sink=trace_path)
    res_traced, _, _ = drain(wl, tracer=tracer, metrics=MetricsRegistry())
    tracer.close()
    for a, b in zip(res_plain, res_traced):
        assert a.count == b.count and a.ok == b.ok, \
            ("traced run diverged", a, b)

    # disabled-path bound: one no-op start+end per query is what the
    # un-guarded instrumentation sites cost when tracing is off
    n_calls = 200_000
    t0 = time.perf_counter()
    for _ in range(n_calls):
        NULL_TRACER.start("x")
        NULL_TRACER.end(None)
    per_call_s = (time.perf_counter() - t0) / (2 * n_calls)
    calls_per_drain = 2 * len(wl_big) + 16      # submit+flush sites, rounded
    null_overhead = 1.0 + calls_per_drain * per_call_s / max(t_plain, 1e-12)

    return dict(
        n_queries=len(wl_big),
        rounds=rounds,
        repeats=repeats,
        untraced_dispatch_s=t_plain,
        traced_dispatch_s=t_traced,
        traced_overhead=ratio,
        null_call_ns=per_call_s * 1e9,
        null_calls_per_drain=calls_per_drain,
        null_overhead=null_overhead,
        n_spans=tracer.n_completed,
        bit_identical=True,
        trace_path=trace_path,
    )


def dynamic_leg() -> dict:
    """Secondary measurement on the dynamic graph (bucket mode): per-query
    compute carries a ×n_buckets state, so vmap amortises a smaller overhead
    fraction — reported, not enforced."""
    params = LdbcParams(n_persons=N_PERSONS, degree_dist="facebook",
                        dynamic=True, seed=2)
    g = generate_ldbc(params)
    wl = make_workload(g, n_per_template=N_PER_TEMPLATE, seed=SEED)
    server = GraniteServer(g, use_planner=True, budget_s=BUDGET_S)
    seq_s = sum(r.latency_ms for r in server.run_workload(wl)) / 1e3
    sched = BatchScheduler(g, use_planner=True, budget_s=BUDGET_S)
    sched.run(wl, warm=True)
    bat_s = sum(d.service_s for d in sched.last_dispatches)
    return dict(graph=graph_name(params), n_queries=len(wl),
                drain_seq_s=seq_s, drain_batched_s=bat_s,
                throughput_ratio=seq_s / max(bat_s, 1e-12))


def ingest_leg(g) -> dict:
    """Live-graph serving: latency while ingestion advances epoch-pinned
    snapshots vs the same warm drain on a frozen graph.

    A slice of ``g``'s edges is held out, the rest becomes epoch 0 of an
    event log; the held-out edges stream back in across epochs while the
    same workload drains after every ``advance``.  Reported:

      latency_ratio          mean per-epoch live drain / frozen drain — the
                             price of serving during ingestion (delta
                             executables and base-fingerprint plans stay
                             warm, so the band is tight; check_bench pins
                             an absolute ceiling);
      delta_exec_dispatches  groups served by the base+delta executable
                             (must be > 0 — the delta path is exercised);
      frozen_identical       final-epoch answers bit-identical to a fresh
                             scheduler on a from-scratch build of the final
                             graph (asserted here, pinned exactly);
      invalidations          cache entries evicted by the closing
                             compaction (delta-aware: zero during the pure
                             edge-append epochs).
    """
    from repro.graphdata import ingest
    from repro.obs import MetricsRegistry
    from repro.serving import EpochManager

    n_epochs = 3                       # edge-append epochs before compaction
    holdout = max(3 * n_epochs, g.n_edges // 20)
    log, held = ingest.log_from_graph(g, holdout_edges=holdout, seed=SEED)
    per = len(held) // n_epochs
    chunks = [held[i * per:(i + 1) * per] for i in range(n_epochs - 1)]
    chunks.append(held[(n_epochs - 1) * per:])

    mx = MetricsRegistry()
    mgr = EpochManager(log, compact_every=2 * n_epochs, metrics=mx)
    e0 = mgr.seal()
    wl = make_workload(e0.graph, n_per_template=N_PER_TEMPLATE, seed=SEED)
    live = BatchScheduler(e0.graph, use_planner=True, budget_s=BUDGET_S,
                          metrics=mx)
    mgr.attach(live)
    live.run(wl, warm=True)

    frozen_sched = BatchScheduler(e0.graph, use_planner=True,
                                  budget_s=BUDGET_S)
    frozen_sched.run(wl, warm=True)
    frozen_sched.run(wl, warm=True)
    frozen_s = sum(d.service_s for d in frozen_sched.last_dispatches)

    live_s, n_delta, ok = [], 0, True
    for chunk in chunks:
        mgr.ingest(chunk)
        mgr.advance(live)
        res = live.run(wl, warm=True)
        ok = ok and all(r.ok for r in res)
        live_s.append(sum(d.service_s for d in live.last_dispatches))
        n_delta += sum(1 for d in live.last_dispatches if d.delta)
    mgr.advance(live, compact=True)
    res = live.run(wl, warm=True)
    ok = ok and all(r.ok for r in res)

    ref = BatchScheduler(ingest.materialize(log, log.n_epochs),
                         use_planner=True, budget_s=BUDGET_S).run(wl)
    frozen_identical = all(a.count == b.count for a, b in zip(res, ref))
    assert frozen_identical, "live serving diverged from a from-scratch build"

    cache = mx.counter("granite_cache_total", "serving cache events",
                       labelnames=("cache", "event"))
    ratio = float(np.mean(live_s)) / max(frozen_s, 1e-12)
    return dict(
        n_queries=len(wl),
        n_held_edges=len(held),
        n_epochs=mgr.current.id + 1,
        n_compactions=mgr.n_compactions,
        frozen_drain_s=frozen_s,
        live_drain_s_mean=float(np.mean(live_s)),
        latency_ratio=ratio,
        delta_exec_dispatches=n_delta,
        frozen_identical=frozen_identical,
        completion_rate=float(ok),
        exec_invalidations=cache.value(cache="executable",
                                       event="invalidation"),
        plan_invalidations=cache.value(cache="plan", event="invalidation"),
    )


def chaos_leg(g, wl, n_workers: int = 4,
              wal_path: str = "BENCH_chaos_wal.jsonl") -> dict:
    """Fault-tolerant serving under a seeded FaultPlan (the paper's
    completion claim as a measured property).

    The workload drains three times on the partitioned engine against a
    fault-free reference:

      flush 1   10% seeded transient dispatch faults + the FIRST partitioned
                dispatch loses a worker → the whole flush re-plans dense;
      flush 2   the partitioned path is still inside its down window
                (``probe_after``) → dense again, no worker consultations;
      flush 3   the probe dispatch fires, succeeds, and restores the
                partitioned path.

    One query is poisoned (fails deterministically): bisection isolates it
    and quarantines exactly that query each flush, everything else answers.
    Reported/enforced: completion rate (answer-or-structured-reject — must
    be 1.0), bit-identity of every answered query vs the reference,
    retry/quarantine/fallback counts, and goodput vs fault-free (retry
    backoff is ACCOUNTED into the drain, so the ratio prices the faults).

    The crash/recover sub-leg tears a WAL append mid-line (simulated crash
    mid-ingest) and requires ``EpochManager.recover`` to restore the exact
    pre-crash pinned-epoch fingerprint."""
    from repro.graphdata import ingest
    from repro.serving import (EpochManager, FaultPlan, RetryPolicy,
                               TornWriteError)

    ref = BatchScheduler(g, engine="partitioned", n_workers=n_workers,
                         use_planner=True, budget_s=BUDGET_S)
    ref.run(wl, warm=True)
    ref_res = ref.run(wl, warm=True)            # warm reference drain
    ref_drain = sum(d.service_s for d in ref.last_dispatches)
    assert all(r.ok for r in ref_res)

    poison = wl[len(wl) // 2].qry
    plan = FaultPlan(seed=SEED, rates={"dispatch": 0.10},
                     schedule={"worker": {0}},
                     poison=lambda q: q is poison)
    sched = BatchScheduler(g, engine="partitioned", n_workers=n_workers,
                           use_planner=True, budget_s=BUDGET_S,
                           plan_cache=ref.plan_cache,
                           exec_cache=ref.exec_cache,
                           fault_plan=plan, retry=RetryPolicy(seed=SEED))
    flushes, drains, engines = [], [], []
    for _ in range(3):
        res = sched.run(wl, warm=True)
        flushes.append(res)
        drains.append(sum(d.service_s for d in sched.last_dispatches))
        engines.append(sorted({r.engine for r in res if r.status == "done"}))
    n_total = 3 * len(wl)
    n_done = n_quar = 0
    identical = True
    for res in flushes:
        for r, rr in zip(res, ref_res):
            if r.status == "done":
                n_done += 1
                identical = identical and r.count == rr.count
            elif r.status == "quarantined":
                n_quar += 1
    completion_rate = (n_done + n_quar) / n_total
    rep = sched.fault_report()
    # goodput prices the chaos: answered queries per accounted second vs the
    # fault-free drain (backoff penalties and retried dispatches inflate
    # the denominator)
    goodput_ratio = ((n_done / max(sum(drains), 1e-12))
                     / (len(wl) / max(ref_drain, 1e-12)))

    # ---- crash/recover: tear a WAL append mid-line, then recover
    log, held = ingest.log_from_graph(g, holdout_edges=30, seed=SEED)
    log.attach_wal(wal_path,
                   fault_plan=FaultPlan(seed=SEED, schedule={"wal": {15}}))
    mgr = EpochManager(log)
    mgr.seal()                                  # epoch 0 (no WAL consults)
    mgr.ingest(held[:10])
    mgr.seal()                                  # epoch 1
    pre_fp = mgr.current.fingerprint
    torn = False
    try:
        mgr.ingest(held[10:])                   # k=15 tears mid-batch
    except TornWriteError:
        torn = True
    del mgr                                     # the crash
    mgr2 = EpochManager.recover(wal_path)
    recovery_identical = torn and mgr2.current.fingerprint == pre_fp
    assert recovery_identical, "WAL recovery diverged from pre-crash state"
    mgr2.log.close_wal()

    return dict(
        n_queries=len(wl),
        n_flushes=3,
        n_done=n_done,
        completion_rate=completion_rate,
        answers_identical=bool(identical),
        n_retries=rep["n_retries"],
        n_quarantined=rep["n_quarantined"],
        n_timeout=rep["n_timeout"],
        n_fallbacks=rep["n_fallbacks"],
        partitioned_restored=bool(rep["partitioned_available"]),
        engines_per_flush=engines,
        fault_plan=rep["fault_plan"],
        ref_drain_s=ref_drain,
        chaos_drain_s=float(sum(drains)),
        goodput_ratio=float(goodput_ratio),
        recovery=dict(
            recovery_identical=bool(recovery_identical),
            n_recovered_epochs=mgr2.log.n_epochs,
            n_open_survivors=mgr2.log.n_open,
        ),
    )


def run(out_path: str = "BENCH_serving.json") -> dict:
    params = LdbcParams(n_persons=N_PERSONS, degree_dist="facebook",
                        dynamic=False, seed=2)
    g = generate_ldbc(params)
    wl = make_workload(g, n_per_template=N_PER_TEMPLATE, seed=SEED)
    n = len(wl)
    print(f"# serving: {graph_name(params)} — {n} queries "
          f"({N_PER_TEMPLATE}/template), seed={SEED}", flush=True)

    # ---- sequential baseline (run_workload warms per instance first)
    server = GraniteServer(g, use_planner=True, budget_s=BUDGET_S)
    seq_recs = server.run_workload(wl)
    seq_ms = np.asarray([r.latency_ms for r in seq_recs])
    seq_drain_s = float(seq_ms.sum()) / 1e3
    seq_tput = n / max(seq_drain_s, 1e-12)

    # ---- batched drain through the scheduler (warm dispatches)
    sched = BatchScheduler(g, use_planner=True, budget_s=BUDGET_S)
    bat_res = sched.run(wl, warm=True)
    bat_drain_s = sum(d.service_s for d in sched.last_dispatches)
    bat_tput = n / max(bat_drain_s, 1e-12)
    for a, b in zip(seq_recs, bat_res):
        assert a.count == b.count, (a.template, a.count, b.count)
    ratio = bat_tput / seq_tput

    # ---- open-loop replay at a rate the sequential loop cannot sustain
    rate = 2.0 * seq_tput
    replay_sched = BatchScheduler(g, use_planner=True, budget_s=BUDGET_S,
                                  plan_cache=sched.plan_cache,
                                  exec_cache=sched.exec_cache)
    rep = replay_workload(replay_sched, wl, rate_qps=rate, seed=SEED,
                          budget_s=BUDGET_S, warm=True)
    seq_sim = sequential_replay_sim(
        poisson_arrivals(n, rate, np.random.default_rng(SEED)), seq_ms / 1e3)

    # ---- SLO layer: online refit, overload admission sweep, closed loop
    slo = slo_leg(g, wl, sched.exec_cache, bat_drain_s, bat_tput,
                  len(sched.last_dispatches))

    # ---- flight-recorder overhead + trace artifact
    obs = obs_leg(g, wl, sched.exec_cache)

    # ---- live-graph serving: epoch-pinned drains while ingesting
    ing = ingest_leg(g)

    # ---- fault-tolerant serving under a seeded FaultPlan + crash recovery
    chaos = chaos_leg(g, wl)

    report = dict(
        graph=graph_name(params),
        scale=SCALE,
        seed=SEED,
        n_queries=n,
        budget_s=BUDGET_S,
        sequential=dict(
            drain_s=seq_drain_s,
            throughput_qps=seq_tput,
            latency_ms_p50=float(np.percentile(seq_ms, 50)),
            latency_ms_p95=float(np.percentile(seq_ms, 95)),
            latency_ms_p99=float(np.percentile(seq_ms, 99)),
            completion_rate=float(np.mean([r.ok for r in seq_recs])),
        ),
        batched=dict(
            drain_s=bat_drain_s,
            throughput_qps=bat_tput,
            n_dispatches=len(sched.last_dispatches),
            mean_batch=float(np.mean(
                [d.n_real for d in sched.last_dispatches])),
            caches=sched.cache_report(),
        ),
        throughput_ratio=ratio,
        replay=rep.as_dict(),
        replay_sequential_sim=seq_sim,
        slo=slo,
        obs=obs,
        partitioned=partitioned_leg(g, wl, seq_drain_s),
        dynamic_leg=dynamic_leg(),
        ingest=ing,
        chaos=chaos,
    )
    with open(out_path, "w") as f:
        json.dump(report, f, indent=2)
    # emit()'s value column is µs-per-call: per-query drain cost here
    emit("serving/drain_seq_us_per_query", seq_drain_s / n * 1e6, f"n={n}")
    emit("serving/drain_batched_us_per_query", bat_drain_s / n * 1e6,
         f"ratio={ratio:.2f}x;dispatches={len(sched.last_dispatches)}")
    emit("serving/replay_p95_us", rep.latency_ms_p95 * 1e3,
         f"rate={rate:.1f}qps;completion={rep.completion_rate:.3f};"
         f"seq_sim_p95_ms={seq_sim['latency_ms_p95']:.1f}")
    emit("serving/slo_admitted_p99_us", slo["overload"]["admitted_p99_ms"]
         * 1e3,
         f"hit={slo['overload']['admitted_hit_rate']:.3f};"
         f"reject={slo['overload']['reject_rate']:.3f};"
         f"plain_p99_ms={slo['overload']['plain_p99_ms']:.1f};"
         f"refit_err={slo['refit']['online_tail_err']:.3f}"
         f"(static {slo['refit']['static_tail_err']:.3f})")
    emit("serving/obs_traced_dispatch_us_per_query",
         obs["traced_dispatch_s"] / obs["n_queries"] * 1e6,
         f"overhead={obs['traced_overhead']:.3f}x;"
         f"null={obs['null_overhead']:.4f}x;spans={obs['n_spans']}")
    emit("serving/ingest_live_drain_us_per_query",
         ing["live_drain_s_mean"] / ing["n_queries"] * 1e6,
         f"ratio={ing['latency_ratio']:.2f}x;"
         f"delta_dispatches={ing['delta_exec_dispatches']};"
         f"epochs={ing['n_epochs']};"
         f"invalidations={ing['exec_invalidations']:.0f}")
    emit("serving/chaos_drain_us_per_query",
         chaos["chaos_drain_s"] / (3 * chaos["n_queries"]) * 1e6,
         f"completion={chaos['completion_rate']:.3f};"
         f"goodput={chaos['goodput_ratio']:.2f}x;"
         f"retries={chaos['n_retries']};"
         f"quarantined={chaos['n_quarantined']};"
         f"recovered={chaos['recovery']['n_recovered_epochs']}ep")
    print(f"# batched drain throughput {bat_tput:.1f} qps vs sequential "
          f"{seq_tput:.1f} qps → {ratio:.2f}x", flush=True)
    print(f"# wrote {out_path}", flush=True)
    if os.environ.get("BENCH_ENFORCE") == "1":
        if ratio < 2.0:
            print(f"# FAIL: throughput ratio {ratio:.2f}x < 2x", flush=True)
            sys.exit(1)
        # SLO acceptance: at 3x capacity the plain open loop must blow past
        # the deadline while admission holds its admitted queries inside
        # theirs.  The EXACT property (100% of admitted inside the deadline
        # under consistent predictions) is pinned deterministically by
        # tests/test_serving_slo.py on the virtual clock; here dispatches
        # are ~1ms wall-time measurements, so the floor tolerates host
        # jitter: >=80% of admitted hit, p99 within 1.3x of the deadline —
        # still far under the plain open loop's 2.5-4x divergence.
        ov = slo["overload"]
        if ov["admitted_hit_rate"] < 0.8:
            print(f"# FAIL: admitted deadline-hit rate "
                  f"{ov['admitted_hit_rate']:.3f} < 0.8", flush=True)
            sys.exit(1)
        if ov["admitted_p99_ms"] > 1.3 * ov["deadline_ms"]:
            print(f"# FAIL: admitted p99 {ov['admitted_p99_ms']:.1f}ms over "
                  f"1.3x deadline {ov['deadline_ms']:.1f}ms", flush=True)
            sys.exit(1)
        if ov["plain_p99_ms"] <= ov["deadline_ms"]:
            print(f"# FAIL: plain open loop did not diverge "
                  f"(p99 {ov['plain_p99_ms']:.1f}ms <= deadline "
                  f"{ov['deadline_ms']:.1f}ms) — overload rate too low",
                  flush=True)
            sys.exit(1)
        if not ov["reject_rate"] > 0:
            print("# FAIL: admission rejected nothing under 3x overload",
                  flush=True)
            sys.exit(1)
        # live-graph acceptance: serving while ingesting must stay within
        # 3x of the frozen drain (warm delta executables keep it near 1x;
        # the headroom absorbs merged-graph groups re-warming per epoch)
        # and the delta-executable path must actually have been used
        if ing["latency_ratio"] > 3.0:
            print(f"# FAIL: live-serving latency ratio "
                  f"{ing['latency_ratio']:.2f}x > 3x frozen", flush=True)
            sys.exit(1)
        if not ing["delta_exec_dispatches"] > 0:
            print("# FAIL: no group was served by the delta executable",
                  flush=True)
            sys.exit(1)
        # chaos acceptance: the completion claim is EXACT — every query
        # under the seeded FaultPlan answers or is structurally rejected,
        # answered queries are bit-identical to fault-free, and crash
        # recovery restores the exact pre-crash epoch fingerprint
        if chaos["completion_rate"] != 1.0:
            print(f"# FAIL: chaos completion rate "
                  f"{chaos['completion_rate']:.4f} != 1.0", flush=True)
            sys.exit(1)
        if not chaos["answers_identical"]:
            print("# FAIL: a fault-injected answer diverged from the "
                  "fault-free reference", flush=True)
            sys.exit(1)
        if not chaos["recovery"]["recovery_identical"]:
            print("# FAIL: WAL crash recovery diverged from the pre-crash "
                  "epoch fingerprint", flush=True)
            sys.exit(1)
        if not (chaos["n_retries"] > 0 and chaos["n_quarantined"] > 0
                and chaos["n_fallbacks"] > 0):
            print(f"# FAIL: chaos exercised nothing "
                  f"(retries={chaos['n_retries']}, "
                  f"quarantined={chaos['n_quarantined']}, "
                  f"fallbacks={chaos['n_fallbacks']})", flush=True)
            sys.exit(1)
        if not chaos["partitioned_restored"]:
            print("# FAIL: partitioned path never restored after the probe "
                  "window", flush=True)
            sys.exit(1)
    return report


def main():
    enable_compile_cache()
    run()


if __name__ == "__main__":
    main()
