"""Flight-recorder tests: span trees pinned on the virtual clock, the
metrics registry, the degradation-ladder scenarios per rung, and the
cost-model audit reproducing live telemetry from trace data alone.

Everything runs through the production scheduler code path with the
FakeDispatcher virtual clock (zero JAX compilation) except the
bit-identity leg and the measure_supersteps profile, which use real
dispatch on the small graph.
"""
import json
import math
import subprocess
import sys

import numpy as np
import pytest

from repro.graphdata.queries import make_workload
from repro.obs import (MetricsRegistry, NULL_TRACER, NullTracer, StepClock,
                       Tracer, load_jsonl, span_trees)
from repro.obs import audit
from repro.obs.trace import _NULL_SPAN
from repro.serving import (AdmissionPolicy, BatchScheduler, TelemetryBuffer,
                           replay_workload)
from repro.serving.testing import (FakeDispatcher, constant_service_model,
                                   planner_service_model)

pytestmark = pytest.mark.obs


def _fake_sched(graph, **kw):
    kw.setdefault("dispatcher",
                  FakeDispatcher(service_model=constant_service_model(1e-3)))
    return BatchScheduler(graph, **kw)


def _tree_names(root):
    """Depth-first (span-id order) name list of one span tree."""
    out, stack = [], [root]
    while stack:
        rec = stack.pop(0)
        out.append(rec["name"])
        stack = rec["children"] + stack
    return out


# ================================================================= tracer
def test_step_clock_and_span_tree_exact():
    """The exact span tree — ids, parents, trace ids, timestamps — is a
    deterministic test vector under an injected StepClock."""
    t = Tracer(clock=StepClock(start=10.0, step=0.5))
    root = t.start("query", template="Q1")
    a = t.start("admit", parent=root)
    t.end(a, verdict="admit")
    b = t.start("plan", parent=root)
    t.end(b)
    t.end(root, status="done")
    recs = t.records()
    # completion order: admit, plan, query
    assert [r["name"] for r in recs] == ["admit", "plan", "query"]
    assert [r["span_id"] for r in recs] == [1, 2, 0]
    assert [r["parent_id"] for r in recs] == [0, 0, None]
    assert all(r["trace_id"] == 0 for r in recs)
    assert [(r["t_start"], r["t_end"]) for r in recs] == [
        (10.5, 11.0), (11.5, 12.0), (10.0, 12.5)]
    assert recs[0]["attrs"] == {"verdict": "admit"}
    trees = span_trees(recs)
    assert list(trees) == [0]
    assert _tree_names(trees[0]) == ["query", "admit", "plan"]


def test_tracer_ring_and_jsonl_sink_identical(tmp_path):
    """The in-memory ring and the JSONL sink hold the same records, float
    for float (repr round-trip), including numpy attr normalisation."""
    p = str(tmp_path / "t.jsonl")
    t = Tracer(clock=StepClock(), sink=p)
    root = t.start("query", feats=np.array([1.5, 0.25]), n=np.int64(3),
                   flag=np.bool_(True))
    t.end(root, err=np.float64(1 / 3))
    t.close()
    ring = t.records()
    disk = load_jsonl(p)
    assert ring == disk
    assert ring[0]["attrs"] == {"feats": [1.5, 0.25], "n": 3, "flag": True,
                                "err": 1 / 3}
    # export_jsonl writes the same stream
    p2 = str(tmp_path / "t2.jsonl")
    assert t.export_jsonl(p2) == 1
    assert load_jsonl(p2) == disk


def test_tracer_ring_capacity_keeps_newest():
    t = Tracer(clock=StepClock(), capacity=3)
    for i in range(5):
        t.end(t.start(f"s{i}"))
    assert [r["name"] for r in t.records()] == ["s2", "s3", "s4"]
    assert t.n_completed == 5


def test_null_tracer_is_inert():
    assert NULL_TRACER.enabled is False
    span = NULL_TRACER.start("query", template="Q1")
    assert span is _NULL_SPAN
    assert NULL_TRACER.start("другой") is span        # singleton, no alloc
    NULL_TRACER.annotate(span, a=1)
    NULL_TRACER.end(span, b=2)
    assert NULL_TRACER.records() == []
    assert isinstance(NULL_TRACER, NullTracer)


def test_recording_tracer_ignores_null_span_parent():
    """A span parented on the null span starts a NEW trace (the scheduler
    can hand entry.span straight through without checking)."""
    t = Tracer(clock=StepClock())
    root = t.start("plan", parent=_NULL_SPAN)
    assert root.parent_id is None and root.trace_id == root.span_id
    t.end(_NULL_SPAN)                                 # no-op, not recorded
    t.annotate(_NULL_SPAN, x=1)
    assert t.records() == []


# ================================================================ metrics
def test_counter_gauge_histogram_semantics():
    mx = MetricsRegistry()
    c = mx.counter("granite_admission_total", "outcomes",
                   labelnames=("verdict", "rung"))
    c.inc(verdict="admit", rung="")
    c.inc(2, verdict="reject", rung="")
    assert c.value(verdict="admit", rung="") == 1
    assert c.value(verdict="reject", rung="") == 2
    assert c.value(verdict="degrade", rung="x") == 0
    with pytest.raises(ValueError):
        c.inc(-1, verdict="admit", rung="")
    with pytest.raises(ValueError):
        c.inc(verdict="admit")                        # missing label
    g = mx.gauge("granite_queue_depth")
    g.set(7)
    g.set(3)
    assert g.value() == 3
    h = mx.histogram("granite_dispatch_ms")
    for v in (0.05, 1.0, 1.5, 100.0, 1e9):            # 1.0 lands in le="1"
        h.observe(v)
    assert h.count() == 5 and h.sum() == pytest.approx(1e9 + 102.55)
    text = mx.to_prometheus()
    assert 'granite_admission_total{verdict="admit",rung=""} 1' in text
    assert "# TYPE granite_dispatch_ms histogram" in text
    assert 'granite_dispatch_ms_bucket{le="1"} 2' in text     # 0.05 + 1.0
    assert 'granite_dispatch_ms_bucket{le="+Inf"} 5' in text  # 1e9 overflows
    assert "granite_dispatch_ms_count 5" in text


def test_registry_memoises_and_rejects_kind_conflicts():
    mx = MetricsRegistry()
    a = mx.counter("x_total")
    assert mx.counter("x_total") is a
    assert "x_total" in mx and mx["x_total"] is a
    with pytest.raises(ValueError):
        mx.gauge("x_total")


def test_snapshot_round_trips_through_json(tmp_path):
    mx = MetricsRegistry()
    mx.counter("c_total", labelnames=("k",)).inc(k="v")
    mx.histogram("h_ms").observe(2.0)
    p = str(tmp_path / "m.json")
    mx.write(p)
    with open(p) as f:
        snap = json.load(f)
    assert snap == mx.snapshot()
    assert snap["c_total"]["series"] == {"v": 1.0}
    assert snap["h_ms"]["series"][""]["count"] == 1
    prom = str(tmp_path / "m.prom")
    mx.write(prom)
    with open(prom) as f:
        assert "# TYPE h_ms histogram" in f.read()


# ==================================================== scheduler span trees
def test_every_query_gets_one_complete_span_tree(medium_static_graph):
    """Acceptance: a replayed workload under FakeDispatcher yields exactly
    one complete span tree per submitted query — admit through exchange for
    dispatched queries, a sealed rejected root for rejects — with the
    predicted-vs-measured fields populated."""
    wl = make_workload(medium_static_graph, templates=("Q2", "Q4"),
                       n_per_template=4, seed=40) * 3
    tr = Tracer(clock=StepClock())
    probe = _fake_sched(medium_static_graph)
    sched = _fake_sched(
        medium_static_graph, tracer=tr, pad_batches=False,
        admission=AdmissionPolicy(headroom=0.5, degrade_impls=(),
                                  allow_engine_downgrade=False),
        dispatcher=FakeDispatcher(
            service_model=planner_service_model(probe._planner.coeffs)))
    c = 2e-3
    rep = replay_workload(sched, wl, rate_qps=20.0 / c, seed=41, mode="open",
                          deadline_s=4.0 * c)
    assert rep.n_rejected > 0 and rep.n_completed > 0
    trees = span_trees(tr.records())
    roots = [t for t in trees.values() if t["name"] == "query"]
    assert len(roots) == len(wl)                      # one tree per submit
    n_done = n_rej = 0
    for root in roots:
        kinds = set(_tree_names(root))
        status = root["attrs"]["status"]
        assert root["t_end"] is not None              # every root sealed
        assert any(ch["name"] == "admit" for ch in root["children"])
        if status == "rejected":
            n_rej += 1
            assert kinds == {"query", "admit"}
            continue
        n_done += 1
        assert {"admit", "plan", "compile", "dispatch", "superstep",
                "exchange"} <= kinds
        # predicted-vs-measured populated on the dispatch span
        d = [ch for ch in root["children"] if ch["name"] == "dispatch"]
        assert len(d) == 1
        a = d[0]["attrs"]
        for k in ("seq", "batch", "edf_pos", "predicted_ms", "measured_ms",
                  "group_features", "group_predicted_ms",
                  "group_measured_ms", "t_launch", "t_ready"):
            assert a.get(k) is not None, k
        assert a["predicted_ms"] > 0 and a["measured_ms"] > 0
    assert n_rej == rep.n_rejected and n_done == rep.n_completed


def test_span_tree_pinned_exactly_on_virtual_clock(medium_static_graph):
    """One query, FakeDispatcher + StepClock: the whole tree — names, ids,
    parents, start/end ticks, measured ms — is pinned exactly."""
    wl = make_workload(medium_static_graph, templates=("Q2",),
                       n_per_template=1, seed=42)
    n_hops = len(wl[0].qry.e_preds)
    tr = Tracer(clock=StepClock())
    sched = _fake_sched(medium_static_graph, tracer=tr)
    res = sched.run(wl)
    assert res[0].ok
    recs = {r["span_id"]: r for r in tr.records()}
    # submit: root=0 (t=0), admit=1 (t=1..2); flush: plan=2 (3..4),
    # compile=3 (5..6), dispatch=4 (7..), then per hop superstep/exchange
    assert recs[0]["name"] == "query" and recs[0]["t_start"] == 0.0
    assert recs[1]["name"] == "admit"
    assert (recs[1]["parent_id"], recs[1]["t_start"], recs[1]["t_end"]) == \
        (0, 1.0, 2.0)
    assert recs[1]["attrs"]["reason"] == "no admission controller"
    assert recs[2]["name"] == "plan"
    assert (recs[2]["t_start"], recs[2]["t_end"]) == (3.0, 4.0)
    assert recs[2]["attrs"]["plan_cached"] is False
    assert recs[2]["attrs"]["candidates"]             # fresh sweep recorded
    assert recs[3]["name"] == "compile"
    assert recs[3]["attrs"]["cache"] == "hit"         # FakeDispatcher path
    assert recs[4]["name"] == "dispatch" and recs[4]["t_start"] == 7.0
    sid = 5
    for h in range(n_hops):
        ss, ex = recs[sid], recs[sid + 1]
        assert ss["name"] == "superstep" and ss["attrs"]["hop"] == h
        assert ss["parent_id"] == 4
        assert ex["name"] == "exchange" and ex["parent_id"] == ss["span_id"]
        assert (ss["t_start"], ex["t_start"], ex["t_end"], ss["t_end"]) == \
            (8.0 + 4 * h, 9.0 + 4 * h, 10.0 + 4 * h, 11.0 + 4 * h)
        sid += 2
    assert recs[4]["t_end"] == 8.0 + 4 * n_hops
    assert recs[0]["t_end"] == 9.0 + 4 * n_hops
    assert recs[0]["attrs"]["status"] == "done"
    # constant_service_model(1e-3) × batch 1 → exactly 1.0 ms, undiluted
    a = recs[4]["attrs"]
    assert a["measured_ms"] == a["group_measured_ms"] == 1.0
    assert a["batch"] == 1 and a["edf_pos"] == 0 and a["seq"] == 0
    # the group's launch/ready stamps ride on the dispatch span
    assert a["t_ready"] - a["t_launch"] == pytest.approx(1e-3)
    # hop shares sum back to the query's measured time exactly; they are
    # apportioned, never reported as measured
    hops = [recs[5 + 2 * h]["attrs"]["apportioned_ms"]
            for h in range(n_hops)]
    assert sum(hops) == pytest.approx(1.0)
    assert all("measured_ms" not in recs[5 + 2 * h]["attrs"]
               for h in range(n_hops))


def test_failed_group_seals_root_spans(medium_static_graph):
    wl = make_workload(medium_static_graph, templates=("Q2",),
                       n_per_template=2, seed=43)
    tr = Tracer(clock=StepClock())
    fd = FakeDispatcher(fail=lambda queries, engine, impl: True)
    sched = BatchScheduler(medium_static_graph, dispatcher=fd, tracer=tr)
    res = sched.run(wl)
    assert all(not r.ok for r in res)
    roots = [r for r in tr.records() if r["name"] == "query"]
    assert len(roots) == 2
    for r in roots:
        assert r["attrs"]["status"] == "failed"
        assert "injected dispatch failure" in r["attrs"]["error"]
        assert r["t_end"] is not None


def test_traced_flush_leaves_results_unchanged_fake(medium_static_graph):
    """Virtual-clock cross-check: identical ServedResults with and without
    the tracer + metrics attached (the real-dispatch leg is conformance)."""
    wl = make_workload(medium_static_graph, templates=("Q2", "Q4"),
                       n_per_template=3, seed=44)
    plain = _fake_sched(medium_static_graph).run(wl)
    traced = _fake_sched(medium_static_graph, tracer=Tracer(StepClock()),
                         metrics=MetricsRegistry()).run(wl)
    assert [(r.count, r.latency_ms, r.ok) for r in plain] == \
        [(r.count, r.latency_ms, r.ok) for r in traced]


# =========================================================== ladder rungs
def test_ladder_rung_admit_metrics_and_span(medium_static_graph):
    wl = make_workload(medium_static_graph, templates=("Q2",),
                       n_per_template=3, seed=45)
    mx = MetricsRegistry()
    tr = Tracer(clock=StepClock())
    sched = _fake_sched(medium_static_graph, metrics=mx, tracer=tr,
                        admission=AdmissionPolicy(headroom=1.0))
    for inst in wl:
        sched.submit(inst, deadline_s=600.0, now=0.0)
    adm = mx["granite_admission_total"]
    assert adm.value(verdict="admit", rung="") == 3
    assert mx["granite_queue_depth"].value() == 3
    sched.flush()
    assert mx["granite_queue_depth"].value() == 0
    assert mx["granite_dispatched_total"].value() == 3
    assert mx["granite_dispatch_ms"].count() == 1
    assert mx["granite_cache_total"].value(cache="plan", event="miss") == 1
    admits = [r for r in tr.records() if r["name"] == "admit"]
    assert all(r["attrs"]["verdict"] == "admit" and r["attrs"]["rungs"] == []
               for r in admits)


def test_ladder_rung_cheaper_impl(medium_static_graph):
    """Rung 1: with θ_scatter_xla inflated, the pallas lowering is strictly
    cheaper, and a deadline between the two costs degrades with exactly the
    impl rung (quantum disabled)."""
    wl = make_workload(medium_static_graph, templates=("Q2",),
                       n_per_template=2, seed=46)
    mx = MetricsRegistry()
    tr = Tracer(clock=StepClock())
    pol = AdmissionPolicy(headroom=1.0, degrade_impls=("pallas",),
                          allow_engine_downgrade=False,
                          degrade_max_batch=None)
    fd = FakeDispatcher()
    sched = BatchScheduler(medium_static_graph, dispatcher=fd, metrics=mx,
                           tracer=tr, admission=pol)
    sched._planner.coeffs["theta_scatter_xla"] = 10.0
    qry = wl[0].qry
    split = qry.n_vertices - 1
    c_xla = sched._planner.estimate(qry, split, "xla").t_ms / 1e3
    c_pal = sched._planner.estimate(qry, split, "pallas").t_ms / 1e3
    assert c_pal < c_xla
    decs = []
    for inst in wl:
        sched.admission.on_flush()
        decs.append(sched.submit(inst, deadline_s=0.9 * c_xla, now=0.0))
    assert all(d.action == "degrade" and d.rungs == ("impl=pallas",)
               for d in decs)
    adm = mx["granite_admission_total"]
    assert adm.value(verdict="degrade", rung="impl=pallas") == 2
    assert adm.value(verdict="admit", rung="") == 0
    res = sched.flush()
    assert all(r.ok for r in res)
    assert all(c.impl == "pallas" for c in fd.calls)
    admits = [r for r in tr.records() if r["name"] == "admit"]
    assert all(r["attrs"]["verdict"] == "degrade"
               and r["attrs"]["rungs"] == ["impl=pallas"] for r in admits)
    disp = [r for r in tr.records() if r["name"] == "dispatch"]
    assert all(r["attrs"]["impl"] == "pallas" for r in disp)


def test_ladder_rung_engine_downgrade_with_quantum(medium_static_graph):
    """Rungs 2+3: dense→sliced with a bounded dispatch quantum — exact
    counter increments under the compound rung label, chunk sizes capped,
    and the rungs annotated on every admit span."""
    wl = make_workload(medium_static_graph, templates=("Q2",),
                       n_per_template=5, seed=47)
    mx = MetricsRegistry()
    tr = Tracer(clock=StepClock())
    fd = FakeDispatcher()
    sched = BatchScheduler(medium_static_graph, engine="dense",
                           dispatcher=fd, metrics=mx, tracer=tr)
    from repro.serving import AdmissionController
    probe_cost = sched._planner.estimate(
        wl[0].qry, wl[0].qry.n_vertices - 1, "xla").t_ms / 1e3
    sched.admission = AdmissionController(AdmissionPolicy(
        headroom=1.0, degrade_impls=(), allow_engine_downgrade=True,
        sliced_discount=0.5, degrade_max_batch=2))
    decs = []
    for inst in wl:
        sched.admission.on_flush()
        decs.append(sched.submit(inst, deadline_s=0.75 * probe_cost,
                                 now=0.0))
    assert all(d.action == "degrade" for d in decs)
    assert all(d.rungs == ("engine=sliced", "quantum=2") for d in decs)
    adm = mx["granite_admission_total"]
    assert adm.value(verdict="degrade", rung="engine=sliced,quantum=2") == 5
    res = sched.flush()
    assert all(r.ok for r in res)
    assert all(c.engine == "sliced" and c.n_real <= 2 for c in fd.calls)
    assert mx["granite_dispatch_ms"].count() == len(fd.calls) == 3
    assert mx["granite_dispatched_total"].value() == 5
    admits = [r for r in tr.records() if r["name"] == "admit"]
    assert all(r["attrs"]["rungs"] == ["engine=sliced", "quantum=2"]
               for r in admits)
    # EDF positions recorded per chunk
    disp = [r for r in tr.records() if r["name"] == "dispatch"]
    assert sorted({r["attrs"]["edf_pos"] for r in disp}) == [0, 1, 2]


def test_ladder_rung_reject(medium_static_graph):
    wl = make_workload(medium_static_graph, templates=("Q2",),
                       n_per_template=2, seed=48)
    mx = MetricsRegistry()
    tr = Tracer(clock=StepClock())
    sched = _fake_sched(medium_static_graph, metrics=mx, tracer=tr,
                        admission=AdmissionPolicy(
                            headroom=1.0, degrade_impls=(),
                            allow_engine_downgrade=False))
    for inst in wl:
        dec = sched.submit(inst, deadline_s=0.0, now=0.0)
        assert dec.action == "reject"
    assert mx["granite_admission_total"].value(verdict="reject", rung="") == 2
    assert sched.queued == 0
    roots = [r for r in tr.records() if r["name"] == "query"]
    assert len(roots) == 2
    assert all(r["attrs"]["status"] == "rejected" for r in roots)
    admits = [r for r in tr.records() if r["name"] == "admit"]
    assert all(r["attrs"]["verdict"] == "reject"
               and "exceeds" in r["attrs"]["reason"] for r in admits)


def test_refit_and_invalidation_counters(medium_static_graph):
    wl = make_workload(medium_static_graph, templates=("Q2",),
                       n_per_template=4, seed=49)
    mx = MetricsRegistry()
    tb = TelemetryBuffer(refit_every=3, min_samples=3, blend=1.0)
    sched = BatchScheduler(
        medium_static_graph, telemetry=tb, metrics=mx,
        dispatcher=FakeDispatcher(service_model=planner_service_model(
            {k: 2.0 * v for k, v in
             BatchScheduler(medium_static_graph)._planner.coeffs.items()})))
    for _ in range(3):
        sched.run(wl)
    assert tb.n_refits == 1
    assert mx["granite_refit_total"].value() == 1
    assert mx["granite_cache_total"].value(cache="plan",
                                          event="invalidation") == 1
    assert sched.plan_cache.stats.invalidations == 1


def test_replay_metrics(medium_static_graph):
    """The replay harness mirrors its terminal accounting into the registry:
    per-status counters, goodput gauge, deadline-slack histogram."""
    wl = make_workload(medium_static_graph, templates=("Q2",),
                       n_per_template=4, seed=50)
    mx = MetricsRegistry()
    sched = _fake_sched(medium_static_graph, metrics=mx,
                        dispatcher=FakeDispatcher(
                            service_model=constant_service_model(
                                0.0, overhead_s=0.05)))
    rep = replay_workload(sched, wl, mode="closed", max_outstanding=4,
                          deadline_s=0.08)
    st = mx["granite_replay_total"]
    assert st.value(status="done") == rep.n_completed == 4
    assert st.value(status="rejected") == 0
    assert mx["granite_goodput_qps"].value() == pytest.approx(
        rep.goodput_qps)
    assert mx["granite_deadline_slack_ms"].count() == rep.n_completed


# ================================================================== audit
def _traced_refit_run(graph, wl, refit, sink):
    tb = TelemetryBuffer(refit_every=4, min_samples=4, blend=1.0,
                         refit=refit)
    tr = Tracer(clock=StepClock(), sink=sink)
    sched = BatchScheduler(
        graph, telemetry=tb, tracer=tr,
        dispatcher=FakeDispatcher(service_model=planner_service_model(
            {k: 3.0 * v for k, v in
             BatchScheduler(graph)._planner.coeffs.items()})))
    for _ in range(8):
        for inst in wl:
            sched.submit(inst)
        assert all(r.ok for r in sched.flush())
    tr.close()
    return tb, tr


def test_audit_reproduces_live_telemetry_exactly(medium_static_graph,
                                                 tmp_path):
    """The acceptance property: obs/audit reproduces the refit-error
    improvement pinned in test_serving_slo.py from trace data ALONE —
    error stats equal to the live TelemetryBuffer float for float, from the
    ring and from the JSONL file alike."""
    wl = make_workload(medium_static_graph, templates=("Q2", "Q4"),
                       n_per_template=4, seed=11)
    p_on = str(tmp_path / "online.jsonl")
    p_off = str(tmp_path / "static.jsonl")
    tb_on, tr_on = _traced_refit_run(medium_static_graph, wl, True, p_on)
    tb_off, tr_off = _traced_refit_run(medium_static_graph, wl, False, p_off)
    for tb, tr, path in ((tb_on, tr_on, p_on), (tb_off, tr_off, p_off)):
        live = tb.error_stats(tail=4)
        for src in (tr, path, load_jsonl(path)):
            rep = audit.error_report(src, tail=4)
            assert rep["n"] == live["n"] == 16
            # float-for-float: repr round-trip through the JSONL sink
            assert rep["mean_abs_rel_err"] == live["mean_abs_rel_err"]
            assert rep["p90_abs_rel_err"] == live["p90_abs_rel_err"]
            assert rep["tail_mean_abs_rel_err"] == \
                live["tail_mean_abs_rel_err"]
    # the pinned improvement, reproduced offline: θ* = 3θ → static error
    # 2/3; the online refit drives it under 0.05
    e_off = audit.error_report(p_off, tail=4)["tail_mean_abs_rel_err"]
    e_on = audit.error_report(p_on, tail=4)["tail_mean_abs_rel_err"]
    assert e_off == pytest.approx(2 / 3, rel=1e-3)
    assert e_on < 0.05 and e_on < 0.2 * e_off


def test_audit_dispatch_records_dedupe_by_seq(medium_static_graph):
    wl = make_workload(medium_static_graph, templates=("Q2", "Q4"),
                       n_per_template=3, seed=51)
    tr = Tracer(clock=StepClock())
    sched = _fake_sched(medium_static_graph, tracer=tr)
    sched.run(wl)
    rows = audit.dispatch_records(tr)
    assert len(rows) == len(sched.last_dispatches) == 2
    assert [r["seq"] for r in rows] == [0, 1]
    # 6 member dispatch spans collapse to 2 group rows
    assert len(audit.spans_named(tr, "dispatch")) == 6
    for row, d in zip(rows, sorted(sched.last_dispatches,
                                   key=lambda d: d.predicted_ms == 0)):
        assert row["batch"] == d.n_real


def test_audit_drift_flags_perturbed_coefficient(medium_static_graph):
    """Feed service times from θ* = 3θ and the trace-refit θ̂ must drift
    toward θ* on the exercised columns."""
    wl = make_workload(medium_static_graph, templates=("Q2", "Q4"),
                       n_per_template=4, seed=52)
    tr = Tracer(clock=StepClock())
    base = dict(BatchScheduler(medium_static_graph)._planner.coeffs)
    sched = BatchScheduler(
        medium_static_graph, tracer=tr,
        telemetry=TelemetryBuffer(refit=False),
        dispatcher=FakeDispatcher(service_model=planner_service_model(
            {k: 3.0 * v for k, v in base.items()})))
    for _ in range(4):
        sched.run(wl)
    drift = audit.coefficient_drift(tr, coeffs=base)
    moved = {k: v for k, v in drift.items() if v["abs_delta"] > 0}
    assert moved, "no coefficient drifted"
    fitted = audit.refit_from_trace(tr, coeffs=base)
    rows = audit.dispatch_records(tr)
    X = np.stack([np.asarray(r["group_features"]) for r in rows])
    y = np.asarray([r["group_measured_ms"] for r in rows])
    from repro.core.planner import coeff_vector
    pred = X @ coeff_vector(fitted)
    # θ̂ explains the measured times far better than the incumbent
    err_hat = np.abs(pred - y) / y
    err_inc = np.abs(X @ coeff_vector(base) - y) / y
    assert err_hat.mean() < 0.1 * err_inc.mean()


def test_audit_plan_accuracy_from_consistent_trace(medium_static_graph):
    """Service times ARE the planner's own model (θ* = θ): every chosen plan
    is optimal under the trace-refit θ̂, so the paper's within-X% metric
    must come out at 1.0."""
    wl = make_workload(medium_static_graph, templates=("Q2", "Q4"),
                       n_per_template=3, seed=53)
    tr = Tracer(clock=StepClock())
    base = dict(BatchScheduler(medium_static_graph)._planner.coeffs)
    sched = BatchScheduler(
        medium_static_graph, tracer=tr,
        dispatcher=FakeDispatcher(
            service_model=planner_service_model(base)))
    sched.run(wl)
    acc = audit.plan_accuracy(tr, within=0.10, coeffs=base)
    assert acc["n_decisions"] == 2
    assert acc["n_queries"] == len(wl)
    assert acc["frac_within"] == 1.0
    # the trace-refit θ̂ comes from 2 dispatch rows (under-determined
    # least squares), so candidate re-costing reproduces the ranking but
    # not the planner's t_ms bit-for-bit
    assert acc["mean_ratio"] == pytest.approx(1.0, abs=0.05)
    rep = audit.audit_report(tr, coeffs=base)
    assert rep["n_dispatches"] == 2
    assert rep["plan"]["frac_within"] == 1.0
    # θ* = θ → the replayed prediction error is numerically zero
    assert rep["error"]["n"] == 2
    assert rep["error"]["mean_abs_rel_err"] < 1e-6


def test_query_summaries_rollup(medium_static_graph):
    wl = make_workload(medium_static_graph, templates=("Q2",),
                       n_per_template=2, seed=54)
    tr = Tracer(clock=StepClock())
    sched = _fake_sched(medium_static_graph, tracer=tr,
                        admission=AdmissionPolicy(headroom=1.0))
    for inst in wl:
        sched.submit(inst, deadline_s=600.0, now=0.0)
    sched.flush()
    rows = audit.query_summaries(tr)
    assert len(rows) == 2
    for row in rows:
        assert row["template"] == "Q2" and row["status"] == "done"
        assert row["verdict"] == "admit" and row["seq"] == 0
        assert row["predicted_ms"] > 0 and row["measured_ms"] > 0


# ==================================================== measure_supersteps
def test_measure_supersteps_traced_exchange_channels(small_static_graph):
    """The profiler's span tree reports per-hop exchange rows matching the
    canonical hop_exchange_channels rule (and their sum,
    query_exchange_volumes)."""
    from repro.core import engine_partitioned as EP

    wl = make_workload(small_static_graph, templates=("Q2",),
                       n_per_template=1, seed=55)
    qry = wl[0].qry
    tr = Tracer(clock=StepClock())
    prof = EP.measure_supersteps(small_static_graph, qry, n_workers=2,
                                 repeats=1, tracer=tr)
    _, arrays, _ = EP.partition_for(small_static_graph, 2)
    want_rows = EP.hop_exchange_channels(qry, arrays)
    trees = span_trees(tr.records())
    assert len(trees) == 1
    root = next(iter(trees.values()))
    assert root["name"] == "measure_supersteps"
    assert root["attrs"]["n_workers"] == 2
    sss = [c for c in root["children"] if c["name"] == "superstep"]
    assert len(sss) == len(want_rows) == len(qry.e_preds)
    got_total = dict(state=0, extremum=0, etr=0)
    for h, ss in enumerate(sss):
        assert ss["attrs"]["hop"] == h
        assert ss["attrs"]["measured_ms"] > 0
        assert len(ss["attrs"]["per_worker_ms"]) == 2
        ex = [c for c in ss["children"] if c["name"] == "exchange"]
        assert len(ex) == 1
        a = ex[0]["attrs"]
        assert {k: a[k] for k in ("state", "extremum", "etr")} == \
            want_rows[h]
        for k in got_total:
            got_total[k] += a[k]
    assert got_total == EP.query_exchange_volumes(qry, arrays)
    assert prof is not None


# =========================================================== trace_report
def test_trace_report_cli_smoke(medium_static_graph, tmp_path):
    wl = make_workload(medium_static_graph, templates=("Q2", "Q4"),
                       n_per_template=2, seed=56)
    p = str(tmp_path / "trace.jsonl")
    tr = Tracer(clock=StepClock(), sink=p)
    sched = _fake_sched(medium_static_graph, tracer=tr,
                        telemetry=TelemetryBuffer(refit=False),
                        admission=AdmissionPolicy(headroom=1.0))
    for inst in wl:
        sched.submit(inst, deadline_s=600.0, now=0.0)
    sched.flush()
    tr.close()
    import os
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    out = subprocess.run(
        [sys.executable, os.path.join(repo, "scripts", "trace_report.py"),
         p, "--limit", "1", "--audit"],
        capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr
    assert "workload rollup" in out.stdout
    assert "queries: 4" in out.stdout
    assert "cost-model audit" in out.stdout
    assert "frac_within" in out.stdout


# ============================================= conformance: bit identity
@pytest.mark.conformance
def test_traced_results_bit_identical_real_dispatch(small_static_graph):
    """Real dispatch: results with the flight recorder attached are
    bit-identical to the untraced scheduler's, across engines."""
    wl = make_workload(small_static_graph, templates=("Q2", "Q4"),
                       n_per_template=2, seed=57)
    for engine in ("auto", "dense"):
        plain = BatchScheduler(small_static_graph, engine=engine,
                               keep_outputs=True).run(wl, warm=True)
        tr = Tracer(clock=StepClock())
        traced = BatchScheduler(small_static_graph, engine=engine,
                                keep_outputs=True, tracer=tr,
                                metrics=MetricsRegistry()).run(wl, warm=True)
        for a, b in zip(plain, traced):
            assert a.ok and b.ok
            assert np.array_equal(a.total, b.total)
        roots = [r for r in tr.records() if r["name"] == "query"]
        assert len(roots) == len(wl)


# ======================================== profiler spans, stamps, scopes
@pytest.mark.parametrize("fault", [None, "straggler"])
def test_dispatch_stamps_on_virtual_clock(medium_static_graph, fault):
    """FakeDispatcher + StepClock: each unit's stamps are ordered, units
    follow each other, and t_ready - t_launch is the raw dispatch time, a
    straggler's accounted inflation included.  Service times are dyadic
    and the clock's ticks whole, so the float sums are exact."""
    from repro.serving import FaultPlan

    wl = make_workload(medium_static_graph, templates=("Q2", "Q4"),
                       n_per_template=3, seed=70)
    plan = (None if fault is None else
            FaultPlan(schedule={"straggler": {0}}, straggler_factor=2.0))
    sched = _fake_sched(
        medium_static_graph, clock=StepClock(step=1.0), fault_plan=plan,
        dispatcher=FakeDispatcher(service_model=constant_service_model(
            2.0 ** -6)))
    res = sched.run(wl)
    assert all(r.ok for r in res)
    ds = sched.last_dispatches
    assert len(ds) == 2
    for d in ds:
        assert d.t_start <= d.t_launch <= d.t_ready <= d.t_end
        assert d.t_ready - d.t_launch == d.service_s - d.penalty_s
    assert ds[0].t_end <= ds[1].t_start
    want0 = 2.0 ** -6 * (ds[0].n_real + ds[0].n_pad)
    assert ds[0].service_s == (want0 if fault is None else 2 * want0)


def test_dispatch_stamps_measure_real_dispatch(small_static_graph):
    """Real dispatch on the default clock: t_ready - t_launch is exactly
    the measured service time, inside the unit's start and end."""
    wl = make_workload(small_static_graph, templates=("Q2", "Q4"),
                       n_per_template=2, seed=71)
    sched = BatchScheduler(small_static_graph, keep_outputs=True)
    sched.run(wl, warm=True)
    for d in sched.last_dispatches:
        assert d.t_start <= d.t_launch < d.t_ready <= d.t_end
        assert d.t_ready - d.t_launch == d.service_s


def test_flush_phases_on_the_profiler_trace(small_static_graph, tmp_path):
    """One flush under ``jax.profiler``: the host plane holds
    ``sched.flush`` enclosing ``sched.launch`` and ``sched.device_wait``,
    all named from the one vocabulary."""
    import glob

    import jax
    from jax.profiler import ProfileData

    from repro.obs.trace import SCHED_PHASES

    wl = make_workload(small_static_graph, templates=("Q2",),
                       n_per_template=2, seed=72)
    sched = BatchScheduler(small_static_graph)
    sched.run(wl, warm=True)                     # compile outside the trace
    with jax.profiler.trace(str(tmp_path)):
        sched.run(wl)
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    spans = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                spans += [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                          for ev in line.events
                          if ev.name.startswith("sched.")]
    names = {n for n, _, _ in spans}
    assert {"sched.flush", "sched.group", "sched.plan", "sched.plan_tensor",
            "sched.launch", "sched.device_wait", "sched.fetch"} <= names
    assert names <= set(SCHED_PHASES)
    (_, f0, f1), = [s for s in spans if s[0] == "sched.flush"]
    for n, a, b in spans:
        if n in ("sched.launch", "sched.device_wait"):
            assert f0 <= a <= b <= f1


def test_program_names_follow_the_shape(small_static_graph):
    """Each batch program is named after its shape: two templates give two
    names, and the name is the lowered module's."""
    import jax.numpy as jnp

    from repro.core import engine as E
    from repro.core import intervals as iv
    from repro.core import query as Q

    q2, q4 = (make_workload(small_static_graph, templates=(t,),
                            n_per_template=1, seed=73)[0].qry
              for t in ("Q2", "Q4"))
    n2 = E.program_name("dense", q2, 1, E.MODE_STATIC)
    n4 = E.program_name("dense", q4, 1, E.MODE_STATIC)
    assert n2 != n4
    assert n2.startswith("dense_static_h2_paths_s1_")
    assert n4.startswith("dense_static_h4_paths_s1_etr_")
    run = E.batch_executable(small_static_graph, q4, 1, sliced=False)
    g = small_static_graph
    be = jnp.asarray(iv.bucket_edges(g.lifespan[0], g.lifespan[1], 16))
    txt = run.fn.lower(E._prepare_gdev(g),
                       jnp.asarray(np.stack([Q.query_params(q4)] * 2)),
                       be).as_text()
    assert f"module @jit_{n4} " in txt


def test_program_name_is_the_same_in_every_process():
    """The name's digest is a CRC of the shape, not Python's salted
    ``hash``: two interpreters with different hash seeds agree."""
    import os

    code = ("from repro.core import engine as E, query as Q\n"
            "q = Q.PathQuery(v_preds=(Q.VertexPredicate(0), "
            "Q.VertexPredicate(1)), e_preds=(Q.EdgePredicate(2),))\n"
            "print(E.program_name('dense', q, 1, E.MODE_BUCKET))\n")
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    names = set()
    for seed in ("1", "2"):
        env = dict(os.environ, PYTHONHASHSEED=seed, JAX_PLATFORMS="cpu",
                   PYTHONPATH=os.path.join(repo, "src"))
        out = subprocess.run([sys.executable, "-c", code], env=env,
                             capture_output=True, text=True, timeout=300)
        assert out.returncode == 0, out.stderr
        names.add(out.stdout.strip())
    assert len(names) == 1
    assert names.pop().startswith("dense_bucket_h1_paths_s1_")


def test_device_scopes_reach_the_lowered_program(small_static_graph):
    """The superstep phases' ``jax.named_scope`` names are in the lowered
    program's debug locations (op_name metadata on the device)."""
    import jax.numpy as jnp

    from repro.core import engine as E
    from repro.core import intervals as iv
    from repro.core import query as Q
    from repro.obs.trace import DEVICE_SCOPES

    q4 = make_workload(small_static_graph, templates=("Q4",),
                       n_per_template=1, seed=74)[0].qry
    run = E.batch_executable(small_static_graph, q4, 2, sliced=False)
    g = small_static_graph
    be = jnp.asarray(iv.bucket_edges(g.lifespan[0], g.lifespan[1], 16))
    txt = run.fn.lower(E._prepare_gdev(g),
                       jnp.asarray(np.stack([Q.query_params(q4)] * 2)),
                       be).as_text(debug_info=True)
    for name in ("hop_deliver", "edge_pred", "etr_prefix", "vertex_pred",
                 "src_gather", "join"):
        assert name in DEVICE_SCOPES
        assert f"({name})/" in txt or f"/{name}/" in txt, name


def _hop_deliver_scatters(compiled_text: str) -> list:
    """op names of the compiled program's scatters under ``hop_deliver``."""
    import re

    names = [re.search(r'op_name="([^"]*)"', ln)
             for ln in compiled_text.splitlines()
             if re.search(r"\bscatter\(", ln)]
    return [m.group(1) for m in names if m and "hop_deliver" in m.group(1)]


@pytest.mark.parametrize("agg", ["paths", "min"])
def test_dense_hop_delivery_does_not_scatter(small_static_graph, agg):
    """The dense batch program delivers hops by prefix differences and
    segmented scans over the arrival CSR (Q2, and Q2-min's extremum
    channel): no scatter under ``hop_deliver``.  The partitioned program,
    whose per-worker slots are padded, still scatters there."""
    import jax.numpy as jnp

    from repro.core import engine as E
    from repro.core import engine_partitioned as EP
    from repro.core import intervals as iv
    from repro.core import query as Q
    from repro.graphdata.queries import to_minmax

    g = small_static_graph
    inst = make_workload(g, templates=("Q2",), n_per_template=1, seed=74)[0]
    q = inst.qry if agg == "paths" else to_minmax(inst, g).qry
    split = 0 if q.agg_op != Q.AGG_NONE else q.n_vertices - 1
    params = jnp.asarray(np.stack([Q.query_params(q)] * 2))
    be = jnp.asarray(iv.bucket_edges(g.lifespan[0], g.lifespan[1], 16))
    dense = E.batch_executable(g, q, split, sliced=False).fn.lower(
        E._prepare_gdev(g), params, be).compile().as_text()
    assert "hop_deliver" in dense
    assert _hop_deliver_scatters(dense) == []
    gd, pd, _, _ = EP.device_tables(g, 2)
    part = EP.batch_executable(g, q, split, n_workers=2).fn.lower(
        gd, pd, params, be).compile().as_text()
    assert _hop_deliver_scatters(part)
