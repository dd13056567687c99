"""Serving-runtime unit tests: plan-tensor compiler, caches, scheduler
dispatch invariants, workload determinism, batch-aware group planning, and
the open-loop replay harness.  Bit-level scheduler-vs-sequential conformance
lives in test_conformance.py (the serving leg of the matrix)."""
import numpy as np
import pytest

from repro.core import engine as E
from repro.core import query as Q
from repro.core.planner import Planner
from repro.core.stats import GraphStats
from repro.graphdata.queries import QueryInstance, make_workload
from repro.serving import (BatchScheduler, ExecutableCache, PlanCache,
                           compile_plan_tensor, graph_fingerprint,
                           replay_workload)
from repro.serving.compile import pad_batch_size


# ---------------------------------------------------------------- compiler
def test_pad_batch_size_pow2():
    assert [pad_batch_size(n) for n in (1, 2, 3, 4, 5, 8, 9)] == \
        [1, 2, 4, 4, 8, 8, 16]


def test_compile_plan_tensor_padding(medium_static_graph):
    wl = make_workload(medium_static_graph, templates=("Q2",),
                       n_per_template=3, seed=1)
    pt = compile_plan_tensor([i.qry for i in wl])
    assert pt.n_real == 3 and pt.params.shape[0] == 4 and pt.n_pad == 1
    # pad rows repeat the first instance's parameters
    assert np.array_equal(pt.params[3], pt.params[0])
    assert np.array_equal(pt.params[0], Q.query_params(wl[0].qry))


def test_compile_rejects_mixed_shapes(medium_static_graph):
    wl = make_workload(medium_static_graph, templates=("Q2", "Q4"),
                       n_per_template=1, seed=2)
    with pytest.raises(ValueError):
        compile_plan_tensor([wl[0].qry, wl[1].qry])


# ------------------------------------------------------------------ caches
def test_graph_fingerprint_content_keyed(small_static_graph,
                                         medium_static_graph):
    fp1 = graph_fingerprint(small_static_graph)
    assert fp1 == graph_fingerprint(small_static_graph)   # cached + stable
    assert fp1 != graph_fingerprint(medium_static_graph)


def test_steady_state_no_replan_no_retrace(medium_static_graph):
    """Second flush of the same workload shape: every plan and executable
    lookup hits — steady-state serving re-plans and re-traces nothing."""
    wl = make_workload(medium_static_graph, templates=("Q2", "Q4"),
                       n_per_template=4, seed=3)
    plan_cache, exec_cache = PlanCache(), ExecutableCache()
    first = BatchScheduler(medium_static_graph, plan_cache=plan_cache,
                           exec_cache=exec_cache).run(wl)
    assert plan_cache.stats.hits == 0
    p_miss, e_miss = plan_cache.stats.misses, exec_cache.stats.misses
    again = BatchScheduler(medium_static_graph, plan_cache=plan_cache,
                           exec_cache=exec_cache).run(wl)
    assert plan_cache.stats.misses == p_miss
    assert exec_cache.stats.misses == e_miss
    assert plan_cache.stats.hits > 0 and exec_cache.stats.hits > 0
    for a, b in zip(first, again):
        assert a.count == b.count and a.split == b.split


# --------------------------------------------------------------- scheduler
def test_scheduler_groups_mixed_workload(medium_static_graph):
    """A mixed drain (plain + aggregate templates) forms one group per shape
    bucket and serves every group batched — no per-query fallback."""
    wl = make_workload(medium_static_graph, templates=("Q2", "Q4"),
                       n_per_template=5, seed=4)
    wla = make_workload(medium_static_graph, templates=("Q2",),
                        n_per_template=3, seed=5, aggregate=True)
    sched = BatchScheduler(medium_static_graph)
    res = sched.run(wl + wla)
    assert len(res) == len(wl) + len(wla)
    assert len(sched.last_dispatches) == 3          # Q2, Q4, Q2-agg buckets
    assert sorted(d.n_real for d in sched.last_dispatches) == [3, 5, 5]
    by_idx = {i: r for i, r in enumerate(res)}
    for disp in sched.last_dispatches:
        for i in disp.indices:
            assert by_idx[i].batch_size == disp.n_real
    # results in submission order, equal to sequential execution
    for inst, r in zip(wl + wla, res):
        want = E.count_results(medium_static_graph, inst.qry, split=r.split)
        assert r.count == want, (inst.template, r.count, want)


def test_scheduler_aggregate_and_partitioned_batched(small_dynamic_graph):
    """The two classes the legacy batched mode fell back on — aggregates and
    the partitioned engine — dispatch as single vmapped groups."""
    from repro.core import engine_partitioned as EP
    wla = make_workload(small_dynamic_graph, templates=("Q3",),
                        n_per_template=4, seed=6, aggregate=True)
    sched = BatchScheduler(small_dynamic_graph, engine="partitioned",
                           n_workers=2, keep_outputs=True)
    res = sched.run(wla)
    assert len(sched.last_dispatches) == 1
    assert sched.last_dispatches[0].engine == "partitioned"
    assert sched.last_dispatches[0].n_real == 4
    for inst, r in zip(wla, res):
        out = EP.execute(small_dynamic_graph, inst.qry, split=r.split,
                         mode=sched._mode_for(inst.qry),
                         n_buckets=sched.n_buckets, n_workers=2)
        assert np.array_equal(np.asarray(out.total), r.total)
        assert np.array_equal(np.asarray(out.per_vertex), r.per_vertex)


def test_scheduler_failing_group_isolated(medium_static_graph):
    """A group that cannot build (MIN/MAX forced onto the sliced engine) must
    return error results without dropping the other groups in the flush."""
    import dataclasses as dc
    wl = make_workload(medium_static_graph, templates=("Q2",),
                       n_per_template=3, seed=10)
    bad = QueryInstance("Q2-min", dc.replace(
        wl[0].qry, agg_op=Q.AGG_MIN, agg_key=next(iter(
            medium_static_graph.meta["builder"].key_ids.values()))), {})
    sched = BatchScheduler(medium_static_graph, engine="sliced")
    res = sched.run(wl + [bad])
    assert sched.queued == 0
    good, err = res[:3], res[3]
    assert all(r.ok and r.error == "" for r in good)
    assert not err.ok and "sliceable" in err.error
    for inst, r in zip(wl, good):
        assert r.count == E.count_results(medium_static_graph, inst.qry,
                                          split=r.split)


# ------------------------------------------ typed extents on one chip
def _program_names(sched) -> set:
    """``jit_<name>`` of every batched program the scheduler built."""
    return {f"jit_{run.fn.__name__}"
            for run in sched.exec_cache._fns.values()}


def test_dense_runs_typed_groups_on_extents(medium_static_graph):
    """Under ``engine="dense"`` a typed COUNT/NONE group runs the extent
    (sliced) executable and records it; a MIN group of the same template
    stays on the whole graph."""
    from repro.graphdata.queries import to_minmax

    g = medium_static_graph
    wl = make_workload(g, templates=("Q2", "Q4"), n_per_template=3, seed=80)
    wl += make_workload(g, templates=("Q2",), n_per_template=2, seed=81,
                        aggregate=True)
    mins = [to_minmax(i, g) for i in make_workload(
        g, templates=("Q2",), n_per_template=2, seed=82)]
    sched = BatchScheduler(g, engine="dense")
    res = sched.run(wl + mins)
    assert all(r.ok and r.engine == "dense" for r in res)
    by_agg = {}
    for d in sched.last_dispatches:
        qry = (wl + mins)[d.indices[0]].qry
        by_agg.setdefault(qry.agg_op, set()).add(d.extents)
    assert by_agg == {Q.AGG_NONE: {True}, Q.AGG_COUNT: {True},
                      Q.AGG_MIN: {False}}
    names = _program_names(sched)
    assert len(names) == 4
    assert sum(n.startswith("jit_sliced_static_") for n in names) == 3
    assert [n for n in names if n.startswith("jit_dense_")] == [
        next(n for n in names if "_min_" in n)]


def test_dense_keeps_delta_groups_whole_graph(small_dynamic_graph):
    """With a pinned pure-append delta window, ETR-free dense groups run the
    base+delta executable (whole graph, ``extents`` false); ETR groups,
    which cannot run base+delta, take the extents on the epoch graph."""
    from repro.graphdata.ingest import log_from_graph
    from repro.serving import EpochManager

    g = small_dynamic_graph
    log, held = log_from_graph(g, holdout_edges=20, seed=7)
    mgr = EpochManager(log, compact_every=10)
    e0 = mgr.seal()
    wl = make_workload(e0.graph, templates=("Q2", "Q4"), n_per_template=2,
                       seed=83)
    sched = BatchScheduler(e0.graph, engine="dense")
    mgr.attach(sched)
    mgr.ingest(held)
    assert mgr.advance(sched).delta is not None
    res = sched.run(wl)
    assert all(r.ok for r in res)
    rows = {any(e.etr_op != -1 for e in wl[d.indices[0]].qry.e_preds):
            (d.delta, d.extents) for d in sched.last_dispatches}
    assert rows == {False: (True, False), True: (False, True)}


@pytest.mark.parametrize("graph", ["small_static_graph",
                                   "small_dynamic_graph"])
def test_dense_extents_bit_identical_to_whole_graph(request, graph):
    """Every LDBC template, plain and COUNT, static and bucket mode: the
    dense scheduler's answers (on extents) equal the whole-graph batch
    executable's bit for bit."""
    g = request.getfixturevalue(graph)
    wl = make_workload(g, n_per_template=2, seed=84)
    wl += make_workload(g, n_per_template=1, seed=85, aggregate=True)
    sched = BatchScheduler(g, engine="dense", keep_outputs=True)
    res = sched.run(wl)
    assert all(d.extents for d in sched.last_dispatches)
    for inst, r in zip(wl, res):
        assert r.ok, (inst.template, r.error)
        want = E.execute_batch_out(g, [inst.qry], r.split,
                                   sched._mode_for(inst.qry),
                                   sched.n_buckets, sliced=False)
        assert np.array_equal(np.asarray(want.total)[0], r.total), \
            inst.template
        if inst.qry.agg_op != Q.AGG_NONE:
            assert np.array_equal(np.asarray(want.per_vertex)[0],
                                  r.per_vertex), inst.template
    assert any(r.count > 0 for r in res)


def test_sliced_engine_still_forces_extents(medium_static_graph):
    wl = make_workload(medium_static_graph, templates=("Q2",),
                       n_per_template=2, seed=86)
    sched = BatchScheduler(medium_static_graph, engine="sliced")
    assert all(r.ok for r in sched.run(wl))
    assert [d.extents for d in sched.last_dispatches] == [True]
    assert all(n.startswith("jit_sliced_") for n in _program_names(sched))


# ---------------------------------------------------- batch-aware planning
def test_planner_choose_batch_costs_whole_batch(medium_static_graph):
    """choose_batch must minimise the batch-summed cost; estimate_batch sums
    per-instance costs (selectivities differ across instances)."""
    wl = make_workload(medium_static_graph, templates=("Q4",),
                       n_per_template=6, seed=7)
    qs = [i.qry for i in wl]
    planner = Planner(medium_static_graph, GraphStats(medium_static_graph))
    est = planner.choose_batch(qs)
    per_instance = {
        s: sum(planner.estimate(q, s).t_ms for q in qs)
        for s in planner.enumerate_plans(qs[0])
    }
    assert est.t_ms == pytest.approx(min(per_instance.values()))
    assert est.split == min(per_instance, key=per_instance.get)
    with pytest.raises(ValueError):
        wl2 = make_workload(medium_static_graph, templates=("Q2",),
                            n_per_template=1, seed=8)
        planner.choose_batch([qs[0], wl2[0].qry])


def test_scheduler_group_planning_regression(medium_static_graph,
                                             monkeypatch):
    """Regression for the (removed) run_workload_batched planning bug, now
    pinned on its replacement: the scheduler's group split must come from
    the batch-aware planner over ALL group instances, not from the first
    instance alone."""
    from repro.launch.query import GraniteServer
    server = GraniteServer(medium_static_graph, use_planner=True)
    wl = make_workload(medium_static_graph, templates=("Q2", "Q4"),
                       n_per_template=4, seed=9)
    seen = []
    orig = Planner.choose_batch

    def spy(self, queries, *args, **kwargs):
        seen.append(len(queries))
        return orig(self, queries, *args, **kwargs)

    monkeypatch.setattr(Planner, "choose_batch", spy)
    bat = server.run_workload_scheduled(wl, warm=False)
    assert seen == [4, 4]                  # whole group, once per bucket
    seq = server.run_workload(wl)
    for a, b in zip(seq, bat):
        assert a.count == b.count, (a.template, a.count, b.count)
    assert all(r.ok for r in bat)


# ------------------------------------------------------------ determinism
def test_make_workload_deterministic(medium_static_graph):
    wl1 = make_workload(medium_static_graph, n_per_template=3, seed=13)
    wl2 = make_workload(medium_static_graph, n_per_template=3, seed=13)
    wl3 = make_workload(medium_static_graph, n_per_template=3, seed=14)
    assert len(wl1) == len(wl2)
    for a, b in zip(wl1, wl2):
        assert a.template == b.template and a.params == b.params
        assert np.array_equal(Q.query_params(a.qry), Q.query_params(b.qry))
    assert any(a.params != c.params for a, c in zip(wl1, wl3))
    # explicit rng generator threads through identically
    wl4 = make_workload(medium_static_graph, n_per_template=3,
                        rng=np.random.default_rng(13))
    for a, d in zip(wl1, wl4):
        assert a.params == d.params


def test_replay_deterministic_schedule(medium_static_graph):
    """Same seed → the same workload and arrival process (the reproducible
    inputs of BENCH_serving.json; batching and wall times legitimately vary
    with measured service speed).  The report counts every query once."""
    wl = make_workload(medium_static_graph, templates=("Q2", "Q4"),
                       n_per_template=4, seed=15)

    def run_once():
        sched = BatchScheduler(medium_static_graph)
        rep = replay_workload(sched, wl, rate_qps=500.0, seed=16, warm=True)
        return rep

    r1, r2 = run_once(), run_once()
    assert r1.n_queries == r2.n_queries == len(wl)
    assert r1.seed == r2.seed
    assert r1.completion_rate == 1.0
    assert np.all(r1.latencies_ms > 0)
    assert r1.latency_ms_p50 <= r1.latency_ms_p95 <= r1.latency_ms_p99
    d = r1.as_dict()
    assert "latencies_ms" not in d and d["n_queries"] == len(wl)
