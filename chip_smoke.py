"""Bring-up smoke of the served query path on a TPU.

One chip (the default):

  phase A  a 100k-person LDBC-SNB F-S graph (static properties), served
           through ``BatchScheduler(engine="dense")``: every
           static template Q1-Q7 plus the MIN variant of Q2 in one drain,
           then a short open-loop replay.  Every query must end ``done``,
           and one instance per template is checked against the
           ``core/ref_engine.py`` oracle within a stated time budget.
  phase B  an F-D graph (dynamic properties) in bucket mode, Q1-Q8, checked
           the same way.

``--chips 4``: only the partitioned path and what it is compared with.
Phase A's graph is served through ``BatchScheduler(engine="partitioned",
n_workers=4)`` (shard_map over a 4-device mesh) and through the dense
engine in the same process; totals must be bit-identical, a subset must
agree with the oracle, the per-worker tables must span the four devices
and the compiled program must contain an all-to-all.

The script exits non-zero on any failure, and when JAX's first device is
not a TPU (it never falls back to the CPU).  Everything it reports goes on
earlier lines; the last line of stdout is one JSON object,
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
Data is generated from ``--seed``; the planner runs on the committed
default cost coefficients.

Run from the repository root:

    python chip_smoke.py [--seed 0] [--chips 4]
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))

#: phase A: the paper's 100k:F person count (F-S graph)
PHASE_A_PERSONS = 100_000
#: phase B: the largest multiple of 10k persons whose batch-8 bucket-mode
#: executables fit one v5e (tests/test_tpu_compile.py rehearses it)
PHASE_B_PERSONS = 20_000
N_PER_TEMPLATE = 8
N_BUCKETS = 16
#: the oracle is pure Python: each checked query may expand at most this
#: many path prefixes, and no new query starts after the phase budget
ORACLE_MAX_EXPANSIONS = 3_000_000
ORACLE_BUDGET_S = 240.0
REPLAY_PER_TEMPLATE = 2
REPLAY_QPS = 50.0
#: the four-chip phase serves one plain, one ETR and one MIN template
PARTITIONED_TEMPLATES = ("Q2", "Q7")


class SmokeFailure(RuntimeError):
    """A phase produced a wrong, missing or failed result."""


def _log(msg: str) -> None:
    print(msg, flush=True)


class CompileClock:
    """Sums JAX's backend-compile durations and persistent-cache hits."""

    def __init__(self):
        import jax

        self.seconds = 0.0
        self.n_compiles = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event: str, secs: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs
            self.n_compiles += 1

    def _on_event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def snapshot(self) -> tuple:
        return self.seconds, self.n_compiles, self.cache_hits

    def since(self, snap: tuple) -> str:
        s, n, h = snap
        return (f"compile {self.seconds - s:.1f} s over "
                f"{self.n_compiles - n} backend compiles "
                f"({self.cache_hits - h} persistent-cache hits)")


def _peak_bytes() -> str:
    import jax

    stats = jax.devices()[0].memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    return "not reported" if peak is None else f"{peak} B"


def _graph(n_persons: int, dynamic: bool, seed: int, tag: str):
    from repro.graphdata.ldbc import LdbcParams, generate_ldbc, graph_name

    params = LdbcParams(n_persons=n_persons, degree_dist="facebook",
                        dynamic=dynamic, seed=seed)
    t0 = time.perf_counter()
    g = generate_ldbc(params)
    _log(f"{tag}: graph {graph_name(params)}: {g.n_vertices} vertices, "
         f"{g.n_edges} edges, {int(g.traversal['t_dst'].shape[0])} traversal "
         f"edges; generated in {time.perf_counter() - t0:.1f} s")
    return g


def _scheduler(g, **kw):
    from repro.core.planner import DEFAULT_COEFFS
    from repro.serving import BatchScheduler

    return BatchScheduler(g, n_buckets=N_BUCKETS,
                          keep_outputs=True, coeffs=dict(DEFAULT_COEFFS), **kw)


def _drain(sched, workload, tag: str, clock: CompileClock) -> list:
    """One warm drain; every query must end ``done``."""
    import numpy as np

    snap = clock.snapshot()
    t0 = time.perf_counter()
    res = sched.run(workload, warm=True)
    wall = time.perf_counter() - t0
    bad = [(r.template, r.status, r.error) for r in res if r.status != "done"]
    if bad:
        raise SmokeFailure(f"{tag}: {len(bad)} of {len(res)} queries not "
                           f"done, e.g. {bad[:3]}")
    disp = sched.last_dispatches
    service = [d.service_s for d in disp]
    nonzero = sum(1 for r in res if r.count != 0)
    _log(f"{tag}: drained {len(res)} queries, all done, in {len(disp)} "
         f"dispatches; {nonzero} of {len(res)} answers non-zero; wall "
         f"{wall:.1f} s, {clock.since(snap)}; dispatch p50 "
         f"{1e3 * float(np.median(service)):.3f} ms; "
         f"{sched.cache_report()['n_executables']} executables")
    return res


def _agrees(ref, inst, r, mode: int) -> tuple:
    """(answer equals the oracle's, oracle answer is non-zero)."""
    import numpy as np

    from repro.core import query as Q

    q = inst.qry
    if q.agg_op == Q.AGG_NONE:
        want = np.asarray(ref.count(q, mode=mode, n_buckets=N_BUCKETS),
                          np.float64)
        got = np.asarray(r.total, np.float64).reshape(want.shape)
        return bool(np.array_equal(got, want)), bool(np.any(want))
    # MIN/MAX group by the first vertex (static mode), as the oracle does
    want = ref.aggregate(q, mode=mode)
    got = {int(i): float(r.minmax[i]) for i in np.nonzero(r.per_vertex)[0]}
    return got == want, bool(want)


def _oracle_check(g, workload, results, mode: int, first: tuple,
                  required, tag: str,
                  budget_s: float = ORACLE_BUDGET_S) -> None:
    """Check one instance per template against the oracle: every template
    in ``first``, then the rest until ``budget_s`` is spent.  Each
    template's instance is its first with a non-zero served answer.
    ``required(checked)`` names what the checked set must contain."""
    from repro.core.ref_engine import RefEngine

    t0 = time.perf_counter()
    ref = RefEngine(g, max_expansions=ORACLE_MAX_EXPANSIONS)
    picks = {}
    for inst, r in zip(workload, results):
        if inst.template not in picks or (picks[inst.template][1].count == 0
                                          and r.count != 0):
            picks[inst.template] = (inst, r)
    order = [t for t in first if t in picks]
    order += [t for t in picks if t not in order]
    checked, skipped = {}, []
    for t in order:
        if t not in first and time.perf_counter() - t0 > budget_s:
            skipped.append(f"{t} (phase budget spent)")
            continue
        inst, r = picks[t]
        t1 = time.perf_counter()
        try:
            ok, nonzero = _agrees(ref, inst, r, mode)
        except RuntimeError as e:      # the oracle's expansion budget
            skipped.append(f"{t} ({e})")
            continue
        if not ok:
            raise SmokeFailure(f"{tag}: {t} disagrees with the oracle "
                               f"(served {r.count})")
        checked[t] = nonzero
        _log(f"{tag}: oracle agrees on {t}: answer {r.count:g}"
             f"{'' if nonzero else ' (zero)'}, "
             f"{time.perf_counter() - t1:.1f} s")
    missing = required(checked)
    if missing:
        raise SmokeFailure(f"{tag}: oracle check incomplete: {missing}")
    _log(f"{tag}: oracle checked {len(checked)} of {len(picks)} templates "
         f"({sum(checked.values())} non-zero) in "
         f"{time.perf_counter() - t0:.1f} s; not checked: "
         f"{', '.join(skipped) or 'none'}")


def phase_a(seed: int, n_persons: int = PHASE_A_PERSONS,
            n_per_template: int = N_PER_TEMPLATE,
            clock: CompileClock = None) -> None:
    """The main path: F-S graph, dense drain + replay, oracle check."""
    from repro.core import engine as E
    from repro.graphdata.queries import TEMPLATES, make_workload, to_minmax
    from repro.serving import replay_workload

    clock = clock or CompileClock()
    tag = "phase A"
    g = _graph(n_persons, False, seed, tag)
    _log(f"{tag}: scale cut: {n_persons} persons at the generator's "
         f"downscaled per-person ratios (graphdata/ldbc.py LdbcParams: "
         f"4 interests per person where SNB has 23)")
    wl = make_workload(g, n_per_template=n_per_template, seed=seed)
    wl += [to_minmax(i, g) for i in wl if i.template == "Q2"]
    sched = _scheduler(g, engine="dense")
    res = _drain(sched, wl, tag, clock)

    def required(checked):
        etr = [t for t in ("Q4", "Q7") if checked.get(t)]
        return ", ".join(
            m for m, ok in (("an ETR template (Q4/Q7) with a non-zero "
                             "answer", etr), ("Q2-min", "Q2-min" in checked))
            if not ok)

    _oracle_check(g, wl, res, E.MODE_STATIC, ("Q7", "Q2-min", "Q4"),
                  required, tag)
    wl_r = make_workload(g, templates=TEMPLATES,
                         n_per_template=REPLAY_PER_TEMPLATE, seed=seed + 1)
    snap = clock.snapshot()
    rep = replay_workload(sched, wl_r, rate_qps=REPLAY_QPS, seed=seed,
                          warm=True)
    if rep.n_completed != rep.n_queries or rep.failures:
        raise SmokeFailure(f"{tag}: replay completed {rep.n_completed} of "
                           f"{rep.n_queries}: {rep.failures[:3]}")
    _log(f"{tag}: open-loop replay of {rep.n_queries} queries at "
         f"{REPLAY_QPS:g} qps: all done in {rep.n_dispatches} dispatches; "
         f"latency p50 {rep.latency_ms_p50:.3f} ms, p99 "
         f"{rep.latency_ms_p99:.3f} ms; {clock.since(snap)}")
    _log(f"{tag}: device peak_bytes_in_use {_peak_bytes()}")


def phase_b(seed: int, n_persons: int = PHASE_B_PERSONS,
            n_per_template: int = N_PER_TEMPLATE,
            clock: CompileClock = None) -> None:
    """Temporal mode: F-D graph in bucket mode, Q1-Q8, oracle check."""
    from repro.core import engine as E
    from repro.graphdata.queries import make_workload

    clock = clock or CompileClock()
    tag = "phase B"
    _log(f"{tag}: size chosen: {n_persons} persons (bucket mode, "
         f"{N_BUCKETS} buckets, batch {n_per_template})")
    g = _graph(n_persons, True, seed, tag)
    wl = make_workload(g, n_per_template=n_per_template, seed=seed)
    sched = _scheduler(g, engine="dense")
    if sched.mode != E.MODE_BUCKET:
        raise SmokeFailure(f"{tag}: scheduler mode {sched.mode}, not bucket")
    res = _drain(sched, wl, tag, clock)

    def required(checked):
        return "" if any(checked.values()) else "a non-zero answer"

    _oracle_check(g, wl, res, E.MODE_BUCKET, ("Q8",), required, tag)
    _log(f"{tag}: device peak_bytes_in_use {_peak_bytes()}")


def phase_partitioned(seed: int, n_persons: int = PHASE_A_PERSONS,
                      n_workers: int = 4,
                      n_per_template: int = N_PER_TEMPLATE,
                      clock: CompileClock = None) -> None:
    """Phase A's graph on the partitioned engine over ``n_workers``
    devices, against the dense engine in the same process."""
    import jax
    import numpy as np

    from repro.core import engine as E
    from repro.core import engine_partitioned as EP
    from repro.core import intervals as iv
    from repro.graphdata.queries import make_workload, to_minmax
    from repro.serving.compile import compile_plan_tensor

    clock = clock or CompileClock()
    tag = "partitioned"
    g = _graph(n_persons, False, seed, tag)
    wl = make_workload(g, templates=PARTITIONED_TEMPLATES,
                       n_per_template=n_per_template, seed=seed)
    wl += [to_minmax(i, g) for i in wl if i.template == "Q2"]
    t0 = time.perf_counter()
    EP.partition_for(g, n_workers)
    _log(f"{tag}: partitioned into {n_workers} workers in "
         f"{time.perf_counter() - t0:.1f} s")
    part = _scheduler(g, engine="partitioned", n_workers=n_workers)
    if part.n_devices != n_workers:
        raise SmokeFailure(f"{tag}: the worker axis resolved to "
                           f"{part.n_devices} devices, not {n_workers}")
    res_d = _drain(_scheduler(g, engine="dense"), wl, f"{tag} (dense)", clock)
    res_p = _drain(part, wl, f"{tag} (partitioned)", clock)
    for i, (a, b) in enumerate(zip(res_d, res_p)):
        for field in ("total", "per_vertex", "minmax"):
            x, y = getattr(a, field), getattr(b, field)
            if (x is None) != (y is None) or (
                    x is not None and not np.array_equal(x, y)):
                raise SmokeFailure(f"{tag}: query {i} ({a.template}) "
                                   f"{field} differs from the dense engine")
    _log(f"{tag}: all {len(wl)} answers bit-identical to the dense engine")

    gdev, pdev, _ = EP.device_tables(g, n_workers, part.n_devices)
    mesh_devs = set(jax.devices()[:n_workers])
    for name, tree, sharded in (("per-worker", pdev, True),
                                ("graph", gdev, False)):
        for leaf in jax.tree_util.tree_leaves(tree):
            sh = leaf.sharding
            if sh.device_set != mesh_devs or (
                    sharded == sh.is_fully_replicated):
                raise SmokeFailure(f"{tag}: a {name} table is placed "
                                   f"{sh}, not over the {n_workers}-device "
                                   f"mesh")
    _log(f"{tag}: per-worker tables sharded over {len(mesh_devs)} devices, "
         f"graph tables replicated on each")

    disp = next(d for d in part.last_dispatches
                if wl[d.indices[0]].template == "Q7")
    queries = [wl[i].qry for i in disp.indices]
    run = EP.batch_executable(g, queries[0], disp.split, E.MODE_STATIC,
                              N_BUCKETS, n_workers)
    bedges = iv.bucket_edges(g.lifespan[0], g.lifespan[1], N_BUCKETS)
    params = compile_plan_tensor(queries, pad=True).params
    text = run.fn.lower(gdev, pdev, params, bedges).compile().as_text()
    n_a2a = text.count("all-to-all")
    if not n_a2a:
        raise SmokeFailure(f"{tag}: no all-to-all in the compiled program")
    _log(f"{tag}: compiled Q7 program holds {n_a2a} all-to-all and "
         f"{text.count('all-reduce')} all-reduce ops")

    def required(checked):
        return "" if checked.get("Q7") and "Q2-min" in checked else \
            "Q7 with a non-zero answer and Q2-min"

    _oracle_check(g, wl, res_p, E.MODE_STATIC, ("Q7", "Q2-min"), required,
                  tag, budget_s=0.0)
    _log(f"{tag}: device 0 peak_bytes_in_use {_peak_bytes()}")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="graph and workload seed")
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the partitioned path on four chips")
    args = ap.parse_args()

    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        print("chip_smoke: FAIL: the repro package (src/repro) is not next "
              "to this script", file=sys.stderr)
        return 1
    sys.path.insert(0, os.path.join(ROOT, "src"))
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: FAIL: no TPU found (JAX's first device is "
              f"{dev.platform}); this smoke never falls back to the CPU",
              file=sys.stderr)
        return 1
    if len(jax.devices()) < args.chips:
        print(f"chip_smoke: FAIL: --chips {args.chips} but JAX sees "
              f"{len(jax.devices())} devices", file=sys.stderr)
        return 1
    from repro.launch.compile_cache import enable_compile_cache

    _log(f"device: {dev.platform} {dev.device_kind} x{len(jax.devices())}; "
         f"jax {jax.__version__}; compile cache {enable_compile_cache()}")
    clock = CompileClock()
    t0 = time.perf_counter()
    try:
        if args.chips == 4:
            phase_partitioned(args.seed, n_workers=4, clock=clock)
        else:
            phase_a(args.seed, clock=clock)
            phase_b(args.seed, clock=clock)
    except SmokeFailure as e:
        print(f"chip_smoke: FAIL: {e}", file=sys.stderr)
        return 1
    for mod in ("repro.launch.dryrun", "repro.roofline.analysis"):
        if mod in sys.modules:
            print(f"chip_smoke: FAIL: {mod} was imported on the served path",
                  file=sys.stderr)
            return 1
    _log(f"total {time.perf_counter() - t0:.1f} s, {clock.since((0.0, 0, 0))}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
