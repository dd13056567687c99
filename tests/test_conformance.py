"""The conformance matrix as tier-1 tests (smoke scale; ci.sh runs the same
matrix with CONFORMANCE_SCALE=ci: full worker sweep + all ETR operators)."""
import numpy as np
import pytest

import conformance as C
from repro.core import engine as E
from repro.core import query as Q
from repro.core.ref_engine import RefEngine

# tier-1 runs this file at smoke scale; scripts/ci.sh re-selects it BY
# MARKER (`-m conformance`) with CONFORMANCE_SCALE=ci for the full sweep
pytestmark = pytest.mark.conformance

# Parametrization must be collection-time static: list the names the matrix
# generates (the ci-only ETR sweep is appended when the env says so).
_SMOKE_NAMES = [
    "plain-2hop", "plain-bidir", "etr-before", "etr-overlaps",
    "agg-count", "agg-min", "agg-max", "agg-min-2hop", "etr-agg-count",
    "empty-result", "single-vertex",
]
_CI_NAMES = ["etr-starts-before", "etr-after", "etr-starts-after"]
CASE_NAMES = _SMOKE_NAMES + (_CI_NAMES if C.scale() == "ci" else [])


@pytest.fixture(scope="module")
def matrix(small_dynamic_graph):
    cases = C.case_matrix(small_dynamic_graph)
    assert set(CASE_NAMES) <= set(cases), "matrix drifted from CASE_NAMES"
    return cases


@pytest.fixture(scope="module")
def oracle(small_dynamic_graph):
    return RefEngine(small_dynamic_graph)


@pytest.mark.parametrize("mode", C.ALL_MODES)
@pytest.mark.parametrize("name", CASE_NAMES)
def test_conformance_matrix(small_dynamic_graph, matrix, oracle, name, mode):
    C.check_case(small_dynamic_graph, oracle, matrix[name], mode)


def test_matrix_covers_acceptance_surface(matrix):
    """MIN/MAX aggregates and ETR hops must run the full worker sweep, so the
    matrix itself proves the acceptance combinations execute partitioned."""
    for name, case in matrix.items():
        if name.startswith(("agg-min", "agg-max", "etr-")):
            assert case.workers == C.WORKERS_FULL, name
    kinds = set()
    for case in matrix.values():
        kinds.add(("agg", case.qry.agg_op))
        kinds.add(("etr", any(e.etr_op != -1 for e in case.qry.e_preds)))
    assert {("agg", Q.AGG_COUNT), ("agg", Q.AGG_MIN), ("agg", Q.AGG_MAX),
            ("agg", Q.AGG_NONE), ("etr", True), ("etr", False)} <= kinds


def test_matrix_exercises_matches(small_dynamic_graph, matrix):
    """The generated matrix must not be vacuous: most non-empty cases
    produce results in static mode."""
    nonzero = 0
    for name, case in matrix.items():
        if case.expect_empty:
            continue
        out = E.execute(small_dynamic_graph, case.qry, mode=E.MODE_STATIC,
                        n_buckets=C.N_BUCKETS, sliced=False)
        nonzero += float(np.sum(np.asarray(out.total))) > 0
    assert nonzero >= 6, "conformance matrix queries mostly match nothing"


@pytest.mark.parametrize("mode", C.ALL_MODES)
@pytest.mark.parametrize("name", CASE_NAMES)
def test_serving_conformance_matrix(small_dynamic_graph, matrix, name, mode):
    """Serving leg: a batched scheduler dispatch of each matrix cell must be
    bit-identical to the sequential per-query loop on every engine, with the
    whole batch served by ONE vmapped call (zero per-query fallbacks — the
    aggregate and partitioned cells are exactly the ones the legacy batched
    mode fell back on)."""
    C.check_serving_case(small_dynamic_graph, matrix[name], mode)


@pytest.mark.parametrize("engine,n_workers", [("dense", 0),
                                              ("partitioned", 2)])
def test_serving_one_program_per_shape(small_dynamic_graph, matrix, engine,
                                       n_workers):
    """Serving representative matrix cells at every batch size 1-8 builds
    one executable per (shape, split, padded batch) and no other, and every
    answer equals ``engine.execute``'s for the same query and split."""
    from repro.serving import BatchScheduler
    from repro.serving.compile import bucket_key

    sched = BatchScheduler(small_dynamic_graph, engine=engine,
                           mode=E.MODE_BUCKET, n_buckets=C.N_BUCKETS,
                           n_workers=max(n_workers, 1), keep_outputs=True)
    programs = set()
    for name in ("plain-2hop", "agg-min"):
        qry = matrix[name].qry
        mode = sched._mode_for(qry)
        for b in range(1, 9):
            queries = C.perturbed_batch(qry, b)
            res = sched.run(queries)
            (d,) = sched.last_dispatches
            assert d.n_real == b and (d.n_real + d.n_pad) in (1, 2, 4, 8)
            programs.add((bucket_key(qry), d.split, d.n_real + d.n_pad))
            for q, r in zip(queries, res):
                out = E.execute(small_dynamic_graph, q, split=r.split,
                                mode=mode, n_buckets=C.N_BUCKETS,
                                sliced=False)
                for field in ("total", "per_vertex", "minmax"):
                    want, got = getattr(out, field), getattr(r, field)
                    if want is None and got is None:
                        continue
                    assert np.array_equal(np.asarray(want), got), (
                        name, b, field)
    assert len(programs) == 8
    assert len(sched.exec_cache) == len(programs)
    assert sched.exec_cache.stats.misses == len(programs)


def test_serving_empty_batch(small_dynamic_graph):
    from repro.serving import BatchScheduler
    sched = BatchScheduler(small_dynamic_graph)
    assert sched.flush() == []
    assert sched.run([]) == []
    assert sched.last_dispatches == []


def test_serving_single_query_batch(small_dynamic_graph, matrix):
    """A batch of one is a degenerate-but-legal group: same result as the
    sequential call, dispatched batched (B padded to 1, no fallback)."""
    from repro.serving import BatchScheduler
    case = matrix["agg-min"]
    sched = BatchScheduler(small_dynamic_graph, mode=E.MODE_STATIC,
                           n_buckets=C.N_BUCKETS, keep_outputs=True)
    (r,) = sched.run([case.qry])
    assert len(sched.last_dispatches) == 1
    assert sched.last_dispatches[0].n_real == 1
    out = E.execute(small_dynamic_graph, case.qry, split=r.split,
                    mode=E.MODE_STATIC, n_buckets=C.N_BUCKETS, sliced=False)
    assert np.array_equal(np.asarray(out.total), r.total)
    assert np.array_equal(np.asarray(out.minmax), r.minmax)


def test_minmax_across_etr_rejected_everywhere(small_dynamic_graph):
    """The one intentionally unsupported combination fails loudly (and
    identically) on the dense AND partitioned paths."""
    from repro.core import engine_partitioned as EP
    b = small_dynamic_graph.meta["builder"]
    vt, et, k = b.v_type_ids, b.e_type_ids, b.key_ids
    qry = Q.PathQuery(
        v_preds=(Q.VertexPredicate(vt["person"]),
                 Q.VertexPredicate(vt["person"]),
                 Q.VertexPredicate(vt["post"])),
        e_preds=(Q.EdgePredicate(et["follows"], Q.DIR_OUT),
                 Q.EdgePredicate(et["created"], Q.DIR_OUT, etr_op=7),),
        agg_op=Q.AGG_MIN, agg_key=k["length"],
    )
    with pytest.raises(NotImplementedError):
        E.execute(small_dynamic_graph, qry, sliced=False)
    with pytest.raises(NotImplementedError):
        EP.execute(small_dynamic_graph, qry, n_workers=2)
