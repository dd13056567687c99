"""Four-way differential conformance harness for the engine stack.

One query runs through every executor and the results must agree bit for bit:

  ref_engine   explicit-path oracle (pure numpy) — defines the semantics
  dense        engine.execute(sliced=False)      — whole-graph supersteps
  sliced       engine.execute(sliced=True)       — type-slice extents
  partitioned  engine_partitioned.execute        — per-worker shards +
                                                   boundary exchange, at
                                                   n_workers ∈ {2, 4, 8}

The matrix (``case_matrix``) spans the full query surface: every aggregate
(COUNT / MIN / MAX), every temporal mode (static / bucket / interval), ETR
and non-ETR hops, empty-result and single-vertex edge cases.  Engine legs
are compared with ``np.array_equal`` — any divergence between executors is a
hard failure, which is what makes the partitioned closure (MIN/MAX extremum
exchange, rank-prefix ETR exchange) safe to ship.

The dense and sliced legs deliver hops by prefix differences over
arrival-sorted edges, the partitioned legs by segment-sum scatters over
padded per-worker slots; agreement between them is exact because engine
counts are integers in float32, so prefix-difference sums equal scatter
sums bit for bit.

Oracle-leg scope (the oracle only *defines* a subset of the surface):
  * path counts: all three modes (float64 enumeration → tolerance compare
    in the temporal modes, exact in static);
  * aggregates: static COUNT/MIN/MAX and bucket COUNT.  Temporal-mode
    MIN/MAX is engine-differential only — the engines' extremum channel is
    gated per hop by *any* live bucket/cell (a documented DP
    over-approximation of per-path liveness), so enumeration is not its
    ground truth.  MIN/MAX across ETR hops is rejected by every engine and
    excluded from the matrix.
  * ETR hops whose operator permits DISJOINT adjacent edge lifespans
    (fully/starts before/after) take the oracle leg in static mode only:
    the tensor engines evaluate temporal validity at bucket granularity, so
    a bucket straddling the gap between two disjoint adjacent edges stays
    live where the oracle's exact-time running intersection is already
    empty.  (First surfaced by this harness — the engines agree with each
    other bit for bit; the divergence is oracle-vs-bucketisation, maximal
    under fully-before.)  OVERLAPS guarantees pairwise-nonempty
    intersections, where bucket and exact granularity coincide on 2-hop
    chains, so it keeps all three oracle modes.

Scale: ``CONFORMANCE_SCALE=smoke`` (default, tier-1) runs partitioned legs
at the workers each case names; ``CONFORMANCE_SCALE=ci`` (scripts/ci.sh)
forces n_workers ∈ {2, 4, 8} everywhere and adds the full ETR-operator
sweep.
"""
from __future__ import annotations

import dataclasses
import os
from typing import Dict, Optional, Sequence, Tuple

import numpy as np

from repro.core import engine as E
from repro.core import engine_partitioned as EP
from repro.core import engine_sliced as ES
from repro.core import intervals as iv
from repro.core import query as Q
from repro.core.ref_engine import RefEngine

ALL_MODES = (E.MODE_STATIC, E.MODE_BUCKET, E.MODE_INTERVAL)
WORKERS_FULL = (2, 4, 8)
WORKERS_SMOKE = (2, 4)
N_BUCKETS = 8


def scale() -> str:
    return os.environ.get("CONFORMANCE_SCALE", "smoke")


@dataclasses.dataclass(frozen=True)
class Case:
    name: str
    qry: Q.PathQuery
    workers: Tuple[int, ...]          # partitioned legs to run
    oracle_modes: Tuple[int, ...]     # modes where the oracle leg applies
    expect_empty: bool = False        # the result must be exactly zero


# =========================================================================
# the generated matrix
# =========================================================================
def case_matrix(graph) -> Dict[str, Case]:
    """Named conformance cases over the LDBC schema of ``graph``.

    Acceptance-critical cases (MIN/MAX aggregates, ETR hops) always carry the
    full worker sweep {2, 4, 8}; the rest use {2, 4} at smoke scale.
    """
    b = graph.meta["builder"]
    vt, et, k = b.v_type_ids, b.e_type_ids, b.key_ids
    cty = b.lookup_value(k["country"], "india")
    person = vt["person"]
    follows = et["follows"]
    created = et["created"]
    wide = WORKERS_FULL
    slim = WORKERS_FULL if scale() == "ci" else WORKERS_SMOKE

    def vp(vtype, *clauses):
        return Q.VertexPredicate(vtype, tuple(clauses))

    cases = {}

    def add(name, qry, workers, oracle_modes=ALL_MODES, expect_empty=False):
        cases[name] = Case(name, qry, workers, oracle_modes, expect_empty)

    # ---- plain paths, non-ETR
    add("plain-2hop", Q.PathQuery(
        v_preds=(vp(person, Q.prop_clause(k["country"], "==", cty)),
                 vp(vt["post"]), vp(person)),
        e_preds=(Q.EdgePredicate(created, Q.DIR_OUT),
                 Q.EdgePredicate(et["likes"], Q.DIR_IN)),
    ), slim)
    add("plain-bidir", Q.PathQuery(
        v_preds=(vp(person), vp(person)),
        e_preds=(Q.EdgePredicate(follows, Q.DIR_BOTH),),
    ), slim)

    # ---- ETR hops (acceptance-critical: full worker sweep).  Operators
    # permitting disjoint adjacent lifespans are oracle-checked in static
    # mode only (bucket-granularity rounding, see module docstring).
    etr_ops = ((iv.FULLY_BEFORE, "before"), (iv.OVERLAPS, "overlaps"))
    if scale() == "ci":
        etr_ops += ((iv.STARTS_BEFORE, "starts-before"),
                    (iv.FULLY_AFTER, "after"),
                    (iv.STARTS_AFTER, "starts-after"))
    for op, tag in etr_ops:
        add(f"etr-{tag}", Q.PathQuery(
            v_preds=(vp(person), vp(person), vp(person)),
            e_preds=(Q.EdgePredicate(follows, Q.DIR_OUT),
                     Q.EdgePredicate(follows, Q.DIR_OUT, etr_op=op)),
        ), wide,
            oracle_modes=(ALL_MODES if op == iv.OVERLAPS
                          else (E.MODE_STATIC,)))

    # ---- aggregates (COUNT; MIN/MAX acceptance-critical)
    add("agg-count", Q.PathQuery(
        v_preds=(vp(person), vp(person)),
        e_preds=(Q.EdgePredicate(follows, Q.DIR_OUT),),
        agg_op=Q.AGG_COUNT,
    ), slim, oracle_modes=(E.MODE_STATIC, E.MODE_BUCKET))
    for op, tag in ((Q.AGG_MIN, "min"), (Q.AGG_MAX, "max")):
        add(f"agg-{tag}", Q.PathQuery(
            v_preds=(vp(person), vp(vt["post"])),
            e_preds=(Q.EdgePredicate(created, Q.DIR_OUT),),
            agg_op=op, agg_key=k["length"],
        ), wide, oracle_modes=(E.MODE_STATIC,))
    add("agg-min-2hop", Q.PathQuery(
        v_preds=(vp(person), vp(person), vp(vt["post"])),
        e_preds=(Q.EdgePredicate(follows, Q.DIR_OUT),
                 Q.EdgePredicate(created, Q.DIR_OUT)),
        agg_op=Q.AGG_MIN, agg_key=k["length"],
    ), wide, oracle_modes=(E.MODE_STATIC,))
    # ETR hop + aggregate: the reversed (right-to-left) segment carries the
    # ETR with backward comparator specs — the partitioned path must agree.
    add("etr-agg-count", Q.PathQuery(
        v_preds=(vp(person), vp(person), vp(person)),
        e_preds=(Q.EdgePredicate(follows, Q.DIR_OUT),
                 Q.EdgePredicate(follows, Q.DIR_IN, etr_op=iv.OVERLAPS)),
        agg_op=Q.AGG_COUNT,
    ), wide, oracle_modes=(E.MODE_STATIC, E.MODE_BUCKET))

    # ---- edge cases
    add("empty-result", Q.PathQuery(
        v_preds=(vp(person, Q.prop_clause(k["country"], "==", 10 ** 6)),
                 vp(person)),
        e_preds=(Q.EdgePredicate(follows, Q.DIR_OUT),),
    ), slim, expect_empty=True)
    add("single-vertex", Q.PathQuery(
        v_preds=(vp(person, Q.prop_clause(k["country"], "==", cty)),),
        e_preds=(),
    ), slim)
    return cases


# =========================================================================
# engine legs + comparison
# =========================================================================
def _np(x):
    return None if x is None else np.asarray(x)


def engine_results(graph, qry: Q.PathQuery, mode: int,
                   workers: Sequence[int] = WORKERS_SMOKE,
                   n_buckets: int = N_BUCKETS,
                   split: Optional[int] = None) -> Dict[str, dict]:
    """Run every applicable executor; returns name → {total, per_vertex,
    minmax} numpy views."""
    legs = {}

    def record(name, out):
        legs[name] = dict(total=_np(out.total), per_vertex=_np(out.per_vertex),
                          minmax=_np(out.minmax))

    record("dense", E.execute(graph, qry, split=split, mode=mode,
                              n_buckets=n_buckets, sliced=False))
    if ES.sliceable(qry):
        record("sliced", E.execute(graph, qry, split=split, mode=mode,
                                   n_buckets=n_buckets, sliced=True))
    for w in workers:
        record(f"partitioned-w{w}",
               EP.execute(graph, qry, split=split, mode=mode,
                          n_buckets=n_buckets, n_workers=w))
    return legs


def assert_engines_identical(legs: Dict[str, dict], ctx=""):
    """Every executor leg must agree bit for bit with the dense leg."""
    ref = legs["dense"]
    for name, got in legs.items():
        if name == "dense":
            continue
        for field in ("total", "per_vertex", "minmax"):
            a, b = ref[field], got[field]
            if a is None and b is None:
                continue
            assert a is not None and b is not None, (ctx, name, field)
            assert np.array_equal(a, b), (ctx, name, field, a, b)


def assert_oracle_counts(oracle: RefEngine, graph, qry, mode, legs,
                         n_buckets=N_BUCKETS, ctx=""):
    want = oracle.count(qry, mode=mode, n_buckets=n_buckets)
    got = legs["dense"]["total"]
    if mode == E.MODE_STATIC or mode == E.MODE_INTERVAL:
        assert float(np.sum(got)) == float(np.sum(want)), (ctx, got, want)
    else:
        np.testing.assert_allclose(got, want, atol=1e-4, err_msg=str(ctx))


def assert_oracle_aggregate(oracle: RefEngine, graph, qry, mode, legs,
                            n_buckets=N_BUCKETS, ctx=""):
    pv = legs["dense"]["per_vertex"]
    if mode == E.MODE_BUCKET:
        assert qry.agg_op == Q.AGG_COUNT, "oracle: bucket aggregates are COUNT"
        want = oracle.aggregate(qry, mode=mode, n_buckets=n_buckets)
        np.testing.assert_allclose(pv, want, atol=1e-4, err_msg=str(ctx))
        return
    assert mode == E.MODE_STATIC, "oracle aggregates: static or bucket COUNT"
    want = oracle.aggregate(qry, mode=mode)
    if qry.agg_op == Q.AGG_COUNT:
        got = {i: float(pv[i]) for i in np.nonzero(pv)[0]}
    else:
        mm = legs["dense"]["minmax"]
        got = {i: float(mm[i]) for i in np.nonzero(pv)[0]}
    assert got == want, (ctx, sorted(got.items())[:5], sorted(want.items())[:5])


def check_case(graph, oracle: Optional[RefEngine], case: Case, mode: int,
               n_buckets: int = N_BUCKETS) -> Dict[str, dict]:
    """Run one (case, mode) cell of the matrix and assert conformance.

    Returns the legs so wrappers can make extra assertions."""
    ctx = (case.name, mode)
    legs = engine_results(graph, case.qry, mode, case.workers, n_buckets)
    assert_engines_identical(legs, ctx)
    if case.expect_empty:
        assert float(np.sum(legs["dense"]["total"])) == 0.0, ctx
    if oracle is not None and mode in case.oracle_modes:
        if case.qry.agg_op == Q.AGG_NONE:
            assert_oracle_counts(oracle, graph, case.qry, mode, legs,
                                 n_buckets, ctx)
        else:
            assert_oracle_aggregate(oracle, graph, case.qry, mode, legs,
                                    n_buckets, ctx)
    return legs


# =========================================================================
# serving leg: batched scheduler vs the sequential per-query loop
# =========================================================================
def perturbed_batch(qry: Q.PathQuery, n: int):
    """Same-shape instance batch: the original query plus n-1 variants with
    shifted clause parameters (values/intervals are DATA in the traced
    program; structure — the shape bucket — is untouched).  Shifted values
    may match nothing, which is exactly the selectivity spread a real
    template workload shows."""
    import dataclasses as dc

    def shift_clause(c: Q.Clause, d: int) -> Q.Clause:
        if c.kind == Q.K_PROP:
            return dc.replace(c, value=c.value + d)
        lo, hi = c.interval
        return dc.replace(c, interval=(max(0, lo - d), hi))

    def shift_query(q: Q.PathQuery, d: int) -> Q.PathQuery:
        v = tuple(dc.replace(vp, clauses=tuple(shift_clause(c, d)
                                               for c in vp.clauses))
                  for vp in q.v_preds)
        e = tuple(dc.replace(ep, clauses=tuple(shift_clause(c, d)
                                               for c in ep.clauses))
                  for ep in q.e_preds)
        return Q.PathQuery(v, e, q.agg_op, q.agg_key)

    batch = [shift_query(qry, d) for d in range(n)]
    assert all(q.shape_key() == qry.shape_key() for q in batch)
    return batch


def serving_engines(case: Case):
    """(engine, n_workers) serving configurations for a case: dense, sliced
    when the query qualifies, and the partitioned engine (full worker sweep
    at ci scale, first worker count at smoke scale)."""
    out = [("dense", 0)]
    if ES.sliceable(case.qry):
        out.append(("sliced", 0))
    workers = case.workers if scale() == "ci" else case.workers[:1]
    out += [("partitioned", w) for w in workers]
    return out


def _sequential_leg(graph, qry, split, mode, n_buckets, engine, n_workers):
    if engine == "partitioned":
        return EP.execute(graph, qry, split=split, mode=mode,
                          n_buckets=n_buckets, n_workers=n_workers)
    return E.execute(graph, qry, split=split, mode=mode, n_buckets=n_buckets,
                     sliced=(engine == "sliced"))


def check_serving_case(graph, case: Case, mode: int,
                       n_buckets: int = N_BUCKETS, batch: int = 3):
    """The serving leg of the matrix: a same-shape batch of ``case``'s query
    through the batch scheduler must be bit-identical to the sequential
    per-query loop, on every engine, dispatched as ONE vmapped group (no
    per-query fallback — aggregates and the partitioned engine included)."""
    from repro.serving import BatchScheduler

    queries = perturbed_batch(case.qry, batch)
    for engine, n_workers in serving_engines(case):
        ctx = (case.name, mode, engine, n_workers)
        sched = BatchScheduler(graph, engine=engine, mode=mode,
                               n_buckets=n_buckets, n_workers=max(n_workers, 1),
                               keep_outputs=True)
        results = sched.run(queries)
        # one group, batched end to end: the zero-fallback invariant
        assert len(sched.last_dispatches) == 1, ctx
        disp = sched.last_dispatches[0]
        assert disp.engine == engine and disp.n_real == len(queries), ctx
        eff_mode = sched._mode_for(case.qry)
        for q, r in zip(queries, results):
            out = _sequential_leg(graph, q, r.split, eff_mode, n_buckets,
                                  engine, n_workers)
            for field, got in (("total", r.total), ("per_vertex", r.per_vertex),
                               ("minmax", r.minmax)):
                want = _np(getattr(out, field))
                if want is None and got is None:
                    continue
                assert want is not None and got is not None, (ctx, field)
                assert np.array_equal(want, got), (ctx, field, want, got)


# =========================================================================
# ingestion leg: epoch-pinned serving vs from-scratch builds
# =========================================================================
def check_ingestion_case(graph, case: Case, mode: int,
                         n_buckets: int = N_BUCKETS, n_epochs: int = 2):
    """The live-graph leg of the matrix: split ``graph`` into a seed epoch
    plus ``n_epochs`` held-out edge batches, serve ``case``'s query through
    an epoch-pinned scheduler while ingestion advances between batches, and
    require every epoch's answers — on dense, sliced (when the query
    qualifies) and the partitioned engine — to be bit-identical to a
    scheduler built from scratch on that epoch's ``materialize`` graph.
    Snapshot isolation is asserted structurally: unsealed events never
    change a pinned scheduler's results."""
    from repro.graphdata import ingest
    from repro.serving import BatchScheduler, EpochManager

    held_n = max(6, 3 * n_epochs)
    log, held = ingest.log_from_graph(graph, holdout_edges=held_n,
                                      seed=hash(case.name) % 1000)
    per = len(held) // n_epochs
    chunks = [held[i * per:(i + 1) * per] for i in range(n_epochs - 1)]
    chunks.append(held[(n_epochs - 1) * per:])
    for engine, n_workers in serving_engines(case):
        ctx = (case.name, mode, engine, n_workers, "ingest")
        mgr = EpochManager(log.clone())
        ep = mgr.seal()
        sched = BatchScheduler(ep.graph, engine=engine, mode=mode,
                               n_buckets=n_buckets,
                               n_workers=max(n_workers, 1))
        mgr.attach(sched)
        for k, chunk in enumerate(chunks, start=1):
            mgr.ingest(chunk)
            # snapshot isolation: the pinned epoch ignores unsealed events
            before = sched.run([case.qry])[0]
            mgr.advance(sched)
            after = sched.run([case.qry])[0]
            ref_graph = ingest.materialize(mgr.log, k + 1)
            ref = BatchScheduler(ref_graph, engine=engine, mode=mode,
                                 n_buckets=n_buckets,
                                 n_workers=max(n_workers, 1)).run(
                                     [case.qry])[0]
            frozen = BatchScheduler(ep.graph, engine=engine, mode=mode,
                                    n_buckets=n_buckets,
                                    n_workers=max(n_workers, 1)).run(
                                        [case.qry])[0]
            for field in ("total", "per_vertex", "minmax"):
                want, got = getattr(ref, field), getattr(after, field)
                if want is None and got is None:
                    continue
                assert np.array_equal(_np(want), _np(got)), (ctx, k, field)
                pre, froz = getattr(before, field), getattr(frozen, field)
                assert np.array_equal(_np(pre), _np(froz)), \
                    (ctx, k, field, "snapshot isolation")
            ep = mgr.current
