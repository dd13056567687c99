"""SLO-layer tests: deadline EDF ordering, admission control (admit /
degrade / reject), online θ refit, and the open- vs closed-loop replay
harness — all on the deterministic FakeDispatcher virtual clock (zero JAX
compilation), plus a real-dispatch bit-identity leg proving the SLO layer
never changes an admitted query's answer.
"""
import dataclasses as dc
import math

import numpy as np
import pytest

from repro.core import engine as E
from repro.core import query as Q
from repro.graphdata.queries import QueryInstance, make_workload
from repro.serving import (AdmissionController, AdmissionPolicy,
                           BatchScheduler, TelemetryBuffer, replay_workload)
from repro.serving.replay import DONE, FAILED, REJECTED
from repro.serving.testing import (FakeDispatcher, constant_service_model,
                                   fake_count, planner_service_model)

pytestmark = pytest.mark.serving


def _sched(graph, **kw):
    kw.setdefault("dispatcher",
                  FakeDispatcher(service_model=constant_service_model(1e-3)))
    return BatchScheduler(graph, **kw)


# ------------------------------------------------------------------- EDF
def test_edf_dispatch_order(medium_static_graph):
    """Groups dispatch earliest-deadline-first: the group deadline is its
    most urgent member, regardless of submission order."""
    wl2 = make_workload(medium_static_graph, templates=("Q2",),
                        n_per_template=3, seed=1)
    wl4 = make_workload(medium_static_graph, templates=("Q4",),
                        n_per_template=3, seed=2)
    sched = _sched(medium_static_graph)
    # Q2 submitted FIRST but with the LATER deadlines
    for inst in wl2:
        sched.submit(inst, deadline_s=50.0, now=0.0)
    for inst in wl4:
        sched.submit(inst, deadline_s=5.0, now=0.0)
    res = sched.flush()
    assert [r.ok for r in res] == [True] * 6
    deadlines = [d.deadline for d in sched.last_dispatches]
    assert deadlines == sorted(deadlines) == [5.0, 50.0]
    # results still return in SUBMISSION order even though dispatch reordered
    for inst, r in zip(wl2 + wl4, res):
        assert r.count == fake_count(inst.qry)


def test_edf_ties_preserve_arrival_order(medium_static_graph):
    """No deadlines → every group ties at +inf and the historical arrival
    order of groups is preserved exactly."""
    wl2 = make_workload(medium_static_graph, templates=("Q2",),
                        n_per_template=2, seed=3)
    wl4 = make_workload(medium_static_graph, templates=("Q4",),
                        n_per_template=2, seed=4)
    fd = FakeDispatcher()
    sched = BatchScheduler(medium_static_graph, dispatcher=fd)
    sched.run([wl4[0], wl2[0], wl4[1], wl2[1]])   # Q4's bucket arrives first
    assert [c.n_real for c in fd.calls] == [2, 2]
    assert fd.calls[0].queries[0] is wl4[0].qry
    assert fd.calls[1].queries[0] is wl2[0].qry
    assert all(d.deadline == math.inf for d in sched.last_dispatches)


def test_mixed_deadline_and_plain_submissions(medium_static_graph):
    """Entries with deadlines outrank the no-deadline (+inf) backlog."""
    wl = make_workload(medium_static_graph, templates=("Q2", "Q4"),
                       n_per_template=2, seed=5)
    sched = _sched(medium_static_graph)
    for inst in wl[:2]:                       # Q2: no deadline
        sched.submit(inst)
    for inst in wl[2:]:                       # Q4: urgent
        sched.submit(inst, deadline_s=1.0, now=0.0)
    sched.flush()
    assert sched.last_dispatches[0].deadline == 1.0
    assert sched.last_dispatches[1].deadline == math.inf


# ------------------------------------------------------------- admission
def _plain_cost_s(sched, qry):
    """What the admission controller predicts for one query at the default
    plan (no cached batch plan yet): the default split."""
    split = 0 if qry.agg_op != Q.AGG_NONE else qry.n_vertices - 1
    return sched._planner_for(sched._engine_for(qry)).estimate(
        qry, split).t_ms / 1e3


def test_admission_admit_then_reject_on_backlog(medium_static_graph):
    """Backlog accounting: identical queries admit until predicted wait +
    service exceeds headroom·deadline, then reject — and a flush resets the
    backlog so admission reopens."""
    wl = make_workload(medium_static_graph, templates=("Q2",),
                       n_per_template=8, seed=6)
    probe = _sched(medium_static_graph)
    cost = _plain_cost_s(probe, wl[0].qry)
    # deadline fits ~3 predicted service times (headroom 1.0 for exactness)
    rel = 3.49 * cost
    pol = AdmissionPolicy(headroom=1.0,
                          allow_engine_downgrade=False)
    sched = _sched(medium_static_graph, admission=pol)
    actions = [sched.submit(inst, deadline_s=rel, now=0.0).action
               for inst in wl]
    n_admit = actions.count("admit")
    assert 1 <= n_admit < len(wl)
    assert actions == ["admit"] * n_admit + ["reject"] * (len(wl) - n_admit)
    assert sched.queued == n_admit and sched.n_rejected == len(wl) - n_admit
    res = sched.flush()
    assert len(res) == n_admit
    # backlog reset: the same query admits again
    assert sched.submit(wl[0], deadline_s=rel, now=1.0).action == "admit"
    rep = sched.slo_report()
    assert rep["n_rejected"] == len(wl) - n_admit
    assert rep["admission"]["n_admitted"] == n_admit + 1


def test_admission_degrades_to_sliced_with_bounded_chunks(
        medium_static_graph):
    """The dense→sliced ladder rung: a deadline between the sliced-discounted
    cost and the dense cost degrades instead of rejecting; degraded entries
    dispatch on the override engine in chunks capped by degrade_max_batch."""
    wl = make_workload(medium_static_graph, templates=("Q2",),
                       n_per_template=5, seed=7)
    fd = FakeDispatcher()
    sched = BatchScheduler(medium_static_graph, engine="dense", dispatcher=fd)
    cost = _plain_cost_s(sched, wl[0].qry)
    pol = AdmissionPolicy(headroom=1.0,
                          allow_engine_downgrade=True, sliced_discount=0.5,
                          degrade_max_batch=2)
    sched.admission = AdmissionController(pol)
    decisions = []
    for inst in wl:
        sched.admission.on_flush()            # isolate: no backlog between
        decisions.append(sched.submit(inst, deadline_s=0.75 * cost, now=0.0))
    assert all(d.action == "degrade" for d in decisions)
    assert all(d.engine == "sliced" and d.max_batch == 2 for d in decisions)
    assert sched.n_degraded == len(wl)
    res = sched.flush()
    assert all(r.ok for r in res)
    assert all(c.engine == "sliced" and c.n_real <= 2 for c in fd.calls)
    assert sum(c.n_real for c in fd.calls) == len(wl)
    for inst, r in zip(wl, res):              # answers survive degradation
        assert r.count == fake_count(inst.qry)


def test_admission_rejects_hopeless_deadline(medium_static_graph):
    wl = make_workload(medium_static_graph, templates=("Q2",),
                       n_per_template=1, seed=8)
    sched = _sched(medium_static_graph, admission=AdmissionPolicy())
    dec = sched.submit(wl[0], deadline_s=0.0, now=0.0)
    assert dec.action == "reject" and not dec.admitted
    assert "exceeds" in dec.reason
    assert sched.queued == 0 and sched.flush() == []


def test_admission_never_writes_plan_cache(medium_static_graph):
    """Admission predicts from plan-cache PEEKs: the batch-aware plan must
    still be computed once per group over ALL members at flush time."""
    wl = make_workload(medium_static_graph, templates=("Q2",),
                       n_per_template=4, seed=9)
    sched = _sched(medium_static_graph, admission=AdmissionPolicy())
    for inst in wl:
        sched.submit(inst, deadline_s=600.0, now=0.0)
    assert len(sched.plan_cache) == 0         # decisions wrote nothing
    assert sched.plan_cache.stats.lookups == 0  # peeks don't count either
    sched.flush()
    assert len(sched.plan_cache) == 1
    assert sched.plan_cache.stats.misses == 1


def test_max_backlog_cap(medium_static_graph):
    """max_backlog_s bounds admitted work even when deadlines are generous."""
    wl = make_workload(medium_static_graph, templates=("Q2",),
                       n_per_template=6, seed=10)
    probe = _sched(medium_static_graph)
    cost = _plain_cost_s(probe, wl[0].qry)
    pol = AdmissionPolicy(headroom=1.0, max_backlog_s=2.5 * cost,
                          allow_engine_downgrade=False)
    sched = _sched(medium_static_graph, admission=pol)
    actions = [sched.submit(inst, deadline_s=600.0, now=0.0).action
               for inst in wl]
    assert actions == ["admit", "admit", "reject", "reject", "reject",
                       "reject"]


# ------------------------------------------------------------- telemetry
def test_online_refit_converges_to_true_theta(medium_static_graph):
    """Service times come from a hidden linear θ* ≠ the live θ: the online
    refit must drive prediction error from ~2/3 (θ* = 3·θ) to ~0, while the
    refit-disabled baseline stays wrong on the same trace."""
    wl = make_workload(medium_static_graph, templates=("Q2", "Q4"),
                       n_per_template=4, seed=11)

    def run(refit: bool) -> TelemetryBuffer:
        tb = TelemetryBuffer(refit_every=4, min_samples=4, blend=1.0,
                             refit=refit)
        sched = BatchScheduler(
            medium_static_graph, telemetry=tb,
            dispatcher=FakeDispatcher(service_model=planner_service_model(
                {k: 3.0 * v for k, v in
                 BatchScheduler(medium_static_graph)._planner.coeffs.items()})))
        for _ in range(8):                    # 8 flushes × 2 groups
            for inst in wl:
                sched.submit(inst)
            assert all(r.ok for r in sched.flush())
        return tb

    online, static = run(True), run(False)
    s_on, s_off = online.error_stats(tail=4), static.error_stats(tail=4)
    assert s_on["n_refits"] >= 1 and s_off["n_refits"] == 0
    assert s_off["tail_mean_abs_rel_err"] == pytest.approx(2 / 3, rel=1e-3)
    assert s_on["tail_mean_abs_rel_err"] < 0.05
    assert s_on["tail_mean_abs_rel_err"] < 0.2 * s_off["tail_mean_abs_rel_err"]


def test_refit_updates_planner_and_clears_plan_cache(medium_static_graph):
    """A refit rewrites the live planner θ in place and invalidates cached
    split choices (they were optimal under the old θ)."""
    wl = make_workload(medium_static_graph, templates=("Q2",),
                       n_per_template=4, seed=12)
    tb = TelemetryBuffer(refit_every=3, min_samples=3, blend=1.0)
    sched = BatchScheduler(
        medium_static_graph, telemetry=tb,
        dispatcher=FakeDispatcher(service_model=planner_service_model(
            {k: 2.0 * v for k, v in
             BatchScheduler(medium_static_graph)._planner.coeffs.items()})))
    theta_before = dict(sched._planner.coeffs)
    for _ in range(2):                        # 2 dispatches: no refit yet
        sched.run(wl)
    assert tb.n_refits == 0 and len(sched.plan_cache) == 1
    sched.run(wl)                             # 3rd dispatch triggers refit
    assert tb.n_refits == 1
    assert len(sched.plan_cache) == 0         # cleared, will re-plan
    assert sched._planner.coeffs != theta_before
    misses = sched.plan_cache.stats.misses
    sched.run(wl)
    assert sched.plan_cache.stats.misses == misses + 1  # re-planned once


def test_telemetry_without_refit_is_pure_recorder(medium_static_graph):
    wl = make_workload(medium_static_graph, templates=("Q2",),
                       n_per_template=2, seed=13)
    tb = TelemetryBuffer(refit=False, refit_every=1, min_samples=2)
    sched = _sched(medium_static_graph, telemetry=tb)
    for _ in range(4):
        sched.run(wl)
    assert len(tb) == 4 and tb.n_refits == 0
    stats = tb.error_stats()
    assert stats["n"] == 4 and stats["n_refits"] == 0


# ---------------------------------------------------------------- replay
def test_replay_empty_workload(medium_static_graph):
    """Regression: n=0 must return a well-formed zero report, not crash in
    np.percentile over an empty array."""
    rep = replay_workload(_sched(medium_static_graph), [], rate_qps=10.0)
    assert rep.n_queries == 0 and rep.n_dispatches == 0
    assert rep.latency_ms_p50 == rep.latency_ms_p99 == 0.0
    assert rep.completion_rate == 0.0 and rep.deadline_hit_rate == 1.0
    assert rep.as_dict()["n_queries"] == 0


def test_replay_single_query(medium_static_graph):
    wl = make_workload(medium_static_graph, templates=("Q2",),
                       n_per_template=1, seed=14)
    rep = replay_workload(_sched(medium_static_graph), wl, rate_qps=10.0)
    assert rep.n_queries == rep.n_completed == 1
    assert rep.completion_rate == 1.0
    assert rep.latency_ms_p50 == rep.latency_ms_p99 > 0


def test_replay_failed_group_not_counted_completed(medium_static_graph):
    """Regression: a failed group's queries used to keep latency 0.0 and
    slip through `lat <= budget` as completed.  They must count FAILED, keep
    NaN latency, and depress the completion rate."""
    wl = make_workload(medium_static_graph, templates=("Q2", "Q4"),
                       n_per_template=3, seed=15)
    fd = FakeDispatcher(fail=lambda queries, engine:
                        queries[0].n_vertices == wl[-1].qry.n_vertices)
    sched = BatchScheduler(medium_static_graph, dispatcher=fd)
    rep = replay_workload(sched, wl, rate_qps=1000.0, seed=16)
    assert rep.n_failed == 3 and rep.n_completed == 3
    assert rep.completion_rate == 0.5
    failed = [i for i, s in enumerate(rep.statuses) if s == FAILED]
    assert len(failed) == 3
    assert np.isnan(rep.latencies_ms[failed]).all()
    assert np.isfinite(rep.latencies_ms[
        [i for i, s in enumerate(rep.statuses) if s == DONE]]).all()


def test_replay_failed_group_real_sliced_engine(small_static_graph):
    """Same regression on the REAL dispatch path: a MIN aggregate forced
    onto the sliced engine fails to build; its replay accounting must say
    failed, not completed."""
    wl = make_workload(small_static_graph, templates=("Q2",),
                       n_per_template=3, seed=17)
    bad = QueryInstance("Q2-min", dc.replace(
        wl[0].qry, agg_op=Q.AGG_MIN, agg_key=next(iter(
            small_static_graph.meta["builder"].key_ids.values()))), {})
    sched = BatchScheduler(small_static_graph, engine="sliced")
    rep = replay_workload(sched, wl + [bad], rate_qps=1000.0, seed=18,
                          warm=True)
    assert rep.n_failed == 1 and rep.n_completed == 3
    assert rep.statuses[3] == FAILED and np.isnan(rep.latencies_ms[3])
    assert rep.completion_rate == 0.75


def test_replay_deadline_hit_accounting(medium_static_graph):
    """Exact virtual-clock arithmetic: two groups, EDF ties → arrival order,
    0.05 s per dispatch; a 0.08 s deadline catches the first dispatch
    (t=0.05) and misses the second (t=0.10)."""
    wl2 = make_workload(medium_static_graph, templates=("Q2",),
                        n_per_template=2, seed=19)
    wl4 = make_workload(medium_static_graph, templates=("Q4",),
                        n_per_template=2, seed=20)
    sched = _sched(medium_static_graph, dispatcher=FakeDispatcher(
        service_model=constant_service_model(0.0, overhead_s=0.05)))
    rep = replay_workload(sched, wl2 + wl4, mode="closed", max_outstanding=4,
                          deadline_s=0.08)
    assert rep.n_completed == 4 and rep.n_dispatches == 2
    assert rep.deadline_hit_rate == 0.5
    assert rep.goodput_qps == pytest.approx(2 / rep.wall_s)
    assert sorted(np.round(rep.latencies_ms, 6)) == [50.0, 50.0, 100.0, 100.0]


def test_open_loop_diverges_closed_loop_bounded(medium_static_graph):
    """The tentpole's control experiment in miniature: at an arrival rate
    beyond capacity, open-loop latency grows with queue depth while the
    closed loop keeps both backlog and batch size bounded."""
    wl = make_workload(medium_static_graph, templates=("Q2",),
                       n_per_template=2, seed=21) * 30
    model = constant_service_model(0.02)      # capacity far below 500 qps

    open_rep = replay_workload(
        _sched(medium_static_graph,
               dispatcher=FakeDispatcher(service_model=model)),
        wl, rate_qps=500.0, seed=22, mode="open")
    closed_rep = replay_workload(
        _sched(medium_static_graph,
               dispatcher=FakeDispatcher(service_model=model)),
        wl, mode="closed", max_outstanding=4)
    assert open_rep.n_completed == closed_rep.n_completed == len(wl)
    # open loop: later arrivals wait behind an ever-deeper queue
    lat = open_rep.latencies_ms
    assert lat[-1] > 3 * lat[0]
    assert open_rep.latency_ms_p99 > 3 * closed_rep.latency_ms_p99
    assert closed_rep.max_batch <= 4 and closed_rep.max_outstanding == 4


def test_admission_holds_deadlines_under_overload(medium_static_graph):
    """Under the same overload, the plain scheduler misses most deadlines
    (open-loop queueing) while the admission-controlled one keeps nearly all
    of its ADMITTED queries inside theirs — trading rejects for goodput.
    Service times come from the planner's own cost model (scale=1), so
    admission's predictions are consistent with the virtual clock."""
    wl = make_workload(medium_static_graph, templates=("Q2",),
                       n_per_template=2, seed=23) * 25
    probe = _sched(medium_static_graph)
    c = float(np.mean([_plain_cost_s(probe, inst.qry) for inst in wl]))
    deadline = 4.0 * c                        # fair for small waves only

    def run(admission):
        sched = _sched(
            medium_static_graph, admission=admission, pad_batches=False,
            dispatcher=FakeDispatcher(
                service_model=planner_service_model(probe._planner.coeffs)))
        return replay_workload(sched, wl, rate_qps=5.0 / c, seed=24,
                               mode="open", deadline_s=deadline)

    plain = run(None)
    slo = run(AdmissionPolicy(headroom=0.5,
                              allow_engine_downgrade=False))
    assert plain.deadline_hit_rate < 0.5          # overload: open loop sinks
    assert slo.n_rejected > 0 and slo.reject_rate > 0
    # admitted queries overwhelmingly finish inside their deadlines
    admitted_lat = slo.latencies_ms[[i for i, s in enumerate(slo.statuses)
                                     if s == DONE]]
    assert admitted_lat.size > 0
    hits = float(np.mean(admitted_lat <= deadline * 1e3 + 1e-6))
    assert hits >= 0.9
    assert slo.deadline_hit_rate > plain.deadline_hit_rate
    assert slo.goodput_qps > plain.goodput_qps
    assert slo.slo["admission"]["n_rejected"] == slo.n_rejected


def test_replay_rejected_queries_excluded(medium_static_graph):
    """Rejected queries never dispatch: statuses say so and the dispatched
    query count matches the admitted population exactly."""
    wl = make_workload(medium_static_graph, templates=("Q2",),
                       n_per_template=2, seed=25) * 10
    probe = _sched(medium_static_graph)
    c = float(np.mean([_plain_cost_s(probe, inst.qry) for inst in wl]))
    fd = FakeDispatcher(service_model=planner_service_model(
        probe._planner.coeffs))
    sched = _sched(medium_static_graph, dispatcher=fd, pad_batches=False,
                   admission=AdmissionPolicy(headroom=1.0,
                                             allow_engine_downgrade=False))
    rep = replay_workload(sched, wl, rate_qps=10.0 / c, seed=26, mode="open",
                          deadline_s=2.0 * c)
    assert rep.n_rejected > 0
    assert rep.n_completed + rep.n_rejected + rep.n_failed == len(wl)
    n_dispatched = sum(c.n_real for c in fd.calls)
    assert n_dispatched == rep.n_completed
    for i, s in enumerate(rep.statuses):
        if s == REJECTED:
            assert np.isnan(rep.latencies_ms[i])
        else:
            assert s == DONE and np.isfinite(rep.latencies_ms[i])


def test_closed_loop_with_admission_frees_rejected_slots(
        medium_static_graph):
    """A closed-loop wave of all-rejects must free its slots and terminate,
    not deadlock the issue loop."""
    wl = make_workload(medium_static_graph, templates=("Q2",),
                       n_per_template=6, seed=31)
    sched = _sched(medium_static_graph, admission=AdmissionPolicy(
        headroom=1.0, allow_engine_downgrade=False))
    rep = replay_workload(sched, wl, mode="closed", max_outstanding=2,
                          deadline_s=0.0)
    assert rep.n_rejected == len(wl) and rep.n_completed == 0
    assert rep.reject_rate == 1.0 and rep.n_dispatches == 0
    assert rep.deadline_hit_rate == 0.0 and rep.goodput_qps == 0.0


# ------------------------------------------- conformance: SLO ≡ plain
@pytest.mark.conformance
def test_slo_scheduler_bit_identical_answers(small_static_graph):
    """Real dispatch: answers from the SLO-layered scheduler (admission +
    telemetry + deadlines, including a forced dense→sliced degrade) are
    bit-identical to the plain scheduler's for every admitted query."""
    wl = make_workload(small_static_graph, templates=("Q2", "Q4"),
                       n_per_template=2, seed=27)
    plain = BatchScheduler(small_static_graph, keep_outputs=True).run(
        wl, warm=True)

    # generous admission: everything admits, telemetry records (no refit —
    # determinism of the comparison, refit correctness is pinned above)
    slo_sched = BatchScheduler(
        small_static_graph, keep_outputs=True,
        admission=AdmissionPolicy(headroom=1.0),
        telemetry=TelemetryBuffer(refit=False))
    for inst in wl:
        slo_sched.submit(inst, deadline_s=600.0, now=0.0)
    slo = slo_sched.flush(warm=True)
    for a, b in zip(plain, slo):
        assert a.ok and b.ok
        assert np.array_equal(a.total, b.total)

    # forced degrade (dense → sliced): still bit-identical where sliceable
    from repro.core import engine_sliced as ES
    sl = [inst for inst in wl if ES.sliceable(inst.qry)]
    assert sl, "workload must contain sliceable queries"
    probe = BatchScheduler(small_static_graph, engine="dense")
    deg_sched = BatchScheduler(
        small_static_graph, engine="dense", keep_outputs=True,
        admission=AdmissionPolicy(headroom=1.0,
                                  allow_engine_downgrade=True,
                                  sliced_discount=0.25,
                                  degrade_max_batch=None))
    decs = []
    for inst in sl:
        deg_sched.admission.on_flush()
        # per-query deadline between its sliced-discounted and dense cost
        decs.append(deg_sched.submit(
            inst, deadline_s=0.5 * _plain_cost_s(probe, inst.qry), now=0.0))
    assert all(d.action == "degrade" and d.engine == "sliced" for d in decs)
    deg = deg_sched.flush(warm=True)
    want = {id(inst): r for inst, r in zip(wl, plain)}
    for inst, r in zip(sl, deg):
        assert r.ok and r.engine == "sliced"
        assert np.array_equal(r.total, want[id(inst)].total)


# -------------------------------------- seeded permutation invariance
def test_flush_results_in_submission_order_any_permutation(
        medium_static_graph):
    """Seeded version of the hypothesis property (runs even without the
    optional dep): under any submission permutation, flush returns each
    query ITS OWN answer, at its submission position."""
    base = make_workload(medium_static_graph, templates=("Q2", "Q4"),
                         n_per_template=4, seed=28)
    base += make_workload(medium_static_graph, templates=("Q2",),
                          n_per_template=2, seed=29, aggregate=True)
    rng = np.random.default_rng(30)
    ref = None
    for _ in range(5):
        perm = rng.permutation(len(base))
        fd = FakeDispatcher()
        res = BatchScheduler(medium_static_graph, dispatcher=fd).run(
            [base[i] for i in perm])
        assert [r.count for r in res] == \
            [fake_count(base[i].qry) for i in perm]
        counts = sorted((c.n_real for c in fd.calls))
        if ref is None:
            ref = counts
        assert counts == ref                  # grouping permutation-invariant


# -------------------------------------- telemetry error_stats edge cases
def test_error_stats_empty_buffer_is_zeroed():
    tb = TelemetryBuffer()
    s = tb.error_stats()
    assert s == dict(n=0, mean_abs_rel_err=0.0, p90_abs_rel_err=0.0,
                     tail_mean_abs_rel_err=0.0, n_refits=0)


def test_error_stats_tail_clamping():
    tb = TelemetryBuffer(refit=False)
    # predicted 1ms, measured 2ms → |1-2|/2 = 0.5 abs rel err per sample
    for _ in range(3):
        tb.record(np.ones(10), 1.0, 2.0)
    full = tb.error_stats()
    assert full["n"] == 3
    assert full["mean_abs_rel_err"] == pytest.approx(0.5)
    # tail longer than the buffer clamps to the whole buffer
    assert tb.error_stats(tail=100) == tb.error_stats(tail=3)
    # tail=0 means "no tail window", not "whole array"
    assert tb.error_stats(tail=0)["tail_mean_abs_rel_err"] == 0.0
    # a genuine tail sees only the newest samples
    tb.record(np.ones(10), 1.0, 1.0)          # perfect prediction
    assert tb.error_stats(tail=1)["tail_mean_abs_rel_err"] == 0.0
    assert tb.error_stats(tail=2)["tail_mean_abs_rel_err"] == \
        pytest.approx(0.25)


# -------------------------------------- injected clock routing (dispatch)
def test_fake_dispatch_duration_equals_service_model_exactly(
        medium_static_graph):
    """The recorded dispatch duration is the fake service model's value
    EXACTLY — timing flows through the injected clock, not time.monotonic."""
    wl = make_workload(medium_static_graph, templates=("Q2", "Q4"),
                       n_per_template=3, seed=60)
    fd = FakeDispatcher(
        service_model=constant_service_model(2e-3, overhead_s=5e-3))
    sched = BatchScheduler(medium_static_graph, dispatcher=fd)
    res = sched.run(wl)
    assert all(r.ok for r in res)
    assert sched.last_dispatches
    for d in sched.last_dispatches:
        # padded batch = n_real + n_pad; == (not approx) — the duration IS
        # the model's output, untouched by any wall clock
        assert d.service_s == 5e-3 + 2e-3 * (d.n_real + d.n_pad)
    # per-query latency is the group time apportioned — still exact algebra:
    # every group's service time is fully distributed over its members
    assert sum(r.latency_ms for r in res) == pytest.approx(
        sum(d.service_s for d in sched.last_dispatches) * 1e3, rel=1e-9)


def test_real_dispatch_duration_comes_from_injected_clock(
        small_static_graph):
    """Real JAX dispatch with a virtual clock on the scheduler: every
    recorded duration is exactly one clock step — proof that _dispatch_jax
    reads self._clock and never the wall clock."""
    from repro.obs import StepClock

    wl = make_workload(small_static_graph, templates=("Q2", "Q4"),
                       n_per_template=2, seed=61)
    sched = BatchScheduler(small_static_graph, clock=StepClock(step=0.125))
    res = sched.run(wl, warm=True)
    assert all(r.ok for r in res)
    assert sched.last_dispatches
    for d in sched.last_dispatches:
        assert d.service_s == 0.125                    # exact, not approx
