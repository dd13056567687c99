"""Granite-JAX temporal path-query engine — dense executor + plan skeleton.

Execution model
---------------
The paper runs one BSP superstep per hop: vertex predicates in ``compute``,
edge predicates + ETR in ``scatter``, partial paths in messages.  Here a
superstep is a dense tensor program over the 2E traversal-edge arrays:

  vertex step : vectorised predicate eval over property columns  → match mask
  edge step   : gather source counts → edge predicate mask → per-edge counts
  delivery    : per-arrival-vertex sums of per-edge counts, as differences
                of a running sum at the arrival CSR offsets (no scatter)

Path multiplicity is carried as float32 *counts* (the tensor form of the
paper's result-tree message compression: per-hop DP state instead of per-path
messages).  Three temporal modes:

  MODE_STATIC    scalar counts               — static temporal graphs
  MODE_BUCKET    counts per time bucket      — dynamic graphs, per-bucket
                 (time-series) semantics; exact per bucket.  Used for the
                 temporal aggregation operator (EQ4-style answers).
  MODE_INTERVAL  counts per running-intersection interval (bucket-pair cells)
                 — dynamic graphs, exact *distinct temporal path* counts on
                 bucket-aligned data.

ETR (edge temporal relationship) hops use precomputed rank tables + segment
prefix sums (see graph.EtrTables): exact, O(E) per hop, no ragged state.

Three-layer architecture
------------------------
The hop primitives (predicate eval, edge masking, ETR rank application,
delivery, state algebra, joins) live in ``superstep.py``; this
module adds the DENSE executor (``run_segment``) plus the split-point plan
skeleton (``execute_plan_traced``) that all executors share via the
``segment_runner`` hook:

  superstep core ──┬── engine.py              dense, whole-graph supersteps
                   ├── engine_sliced.py       type-slice extents per hop
                   └── engine_partitioned.py  per-worker shards + boundary
                                              exchange (distributed path)

``execute()`` routes between dense/sliced; ``engine_partitioned.execute()``
is the partition-sharded entry point with identical semantics.
"""
from __future__ import annotations

import dataclasses
import zlib
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..obs.trace import scope
from . import intervals as iv
from . import query as Q
from . import superstep as SS
from .graph import TemporalGraph
from .superstep import MODE_BUCKET, MODE_INTERVAL, MODE_STATIC

# Back-compat aliases for primitives that moved to superstep.py, kept only
# for the external users that still reach them through this module
# (benchmarks/components.py).  New code should import from superstep.
_TRACE_BEDGES = SS.TRACE_BEDGES  # same list object — push/pop still scopes
_eval_predicate = SS.eval_predicate
_etr_weighted = SS.etr_weighted


# =========================================================================
# segment execution (dense)
# =========================================================================
@dataclasses.dataclass
class SegmentResult:
    arrivals_e: Optional[jnp.ndarray]  # per traversal-edge counts into final vertex
    arrivals_v: Optional[jnp.ndarray]  # segment-sum of the above ([V, *TS])
    stats: List[dict]                  # per-superstep instrumentation
    minmax_v: Optional[jnp.ndarray] = None  # min/max channel at final vertex


def _edge_predicate_weights(gdev, ep: Q.EdgePredicate, params, pbase, mode, bedges):
    """(weight f32[2E], bucket validity or interval validity) for a hop."""
    return SS.edge_predicate_weights(gdev, ep, params, pbase, mode, bedges)


def run_segment(
    gdev: dict,
    v_preds: Sequence[Q.VertexPredicate],
    e_preds: Sequence[Q.EdgePredicate],
    params,
    pbases_v: Sequence[int],
    pbases_e: Sequence[int],
    mode: int,
    n_buckets: int,
    backward: bool,
    with_minmax: bool = False,
    minmax_op: int = Q.AGG_MIN,
    minmax_col=None,
    delta=None,
) -> SegmentResult:
    """Run one path segment.  v_preds has one more entry than e_preds; the
    FINAL vertex predicate is NOT applied (it belongs to the join).

    ``delta`` (a ``graphdata.ingest.DeltaSpec.device()`` dict) adds the
    base+delta execution path: every plain hop also evaluates the edge
    predicate over the delta-edge slots and merges their (unsorted)
    delivery into the base arrivals — bit-identical to running on the
    merged epoch graph, with the base graph's compiled executables untouched.
    ETR hops read global rank tables and are delta-incompatible (callers
    gate on query shape; ``batch_executable_delta`` refuses them).

    Returns raw arrivals (per-edge and per-vertex) at the final vertex.
    """
    V = gdev["v_life"].shape[0]
    stats: List[dict] = []
    bedges = SS.current_bedges()

    # ---- init superstep (first vertex predicate)
    vm, vv = SS.vertex_predicate(gdev, v_preds[0], params, pbases_v[0], mode,
                                 bedges)
    state_v = SS.init_state(vm, vv, mode, n_buckets)
    stats.append(dict(phase="init", matched=jnp.sum(vm)))

    mch_v = None
    if with_minmax:
        vals0, _ = minmax_col
        mch_v = SS.minmax_seed(state_v, vals0, minmax_op, mode)

    arrivals_e = None
    arrivals_v = None
    prev_raw_e = None
    for i, ep in enumerate(e_preds):
        wmask, evalidity = SS.edge_predicate_weights(
            gdev, ep, params, pbases_e[i], mode, bedges
        )
        if i > 0:
            # apply the intermediate vertex predicate (post-arrival)
            vm, vv = SS.vertex_predicate(gdev, v_preds[i], params,
                                         pbases_v[i], mode, bedges)
        if ep.etr_op != -1:
            if delta is not None:
                raise NotImplementedError(
                    "delta execution across ETR hops (global rank tables)")
            # ETR hop: prefix-sum over *raw* previous arrivals, then apply the
            # intermediate vertex predicate at the source gather.
            src_cnt = SS.etr_weighted(gdev, prev_raw_e, ep.etr_op, backward,
                                      use_arr=False)
            with scope("src_gather"):
                t_src = gdev["t_src"]
                if mode == MODE_STATIC:
                    src_val = src_cnt * vm[t_src].astype(jnp.float32)
                elif mode == MODE_BUCKET:
                    src_val = src_cnt * (vm[:, None] & vv)[t_src].astype(
                        jnp.float32)
                else:
                    src_val = SS.apply_validity(src_cnt, vm[t_src],
                                                vv[t_src], mode)
        else:
            if i == 0:
                sv = state_v
            else:
                sv = SS.apply_validity(arrivals_v, vm, vv, mode)
            with scope("src_gather"):
                src_val = sv[gdev["t_src"]]
        cnt_e = SS.apply_edge(src_val, wmask, evalidity, mode)
        arrivals_e = cnt_e
        prev_raw_e = cnt_e
        if with_minmax and ep.etr_op != -1:
            raise NotImplementedError("min/max aggregation across ETR hops")
        d_add = d_mm = None
        if delta is not None:
            # delta-segment contribution, from the SAME pre-hop source state
            # and extremum channel the base delivery reads
            d_add, d_mm = SS.delta_hop_deliver(
                delta, ep, sv, params, pbases_e[i], mode, V,
                mch=(mch_v if with_minmax else None), minmax_op=minmax_op)
        arrivals_v = SS.deliver(cnt_e, gdev["t_dst"], V, ptr=gdev["arr_ptr"])
        if with_minmax:
            with scope("src_gather"):
                m_src = mch_v[gdev["t_src"]]
            m_e = SS.minmax_edge(m_src, cnt_e, minmax_op, mode)
            mch_v = SS.deliver_extremum(m_e, gdev["t_dst"], V, minmax_op,
                                        ptr=gdev["arr_ptr"])
        if d_add is not None:
            arrivals_v = arrivals_v + d_add
            if with_minmax:
                comb = jnp.minimum if minmax_op == Q.AGG_MIN else jnp.maximum
                mch_v = comb(mch_v, d_mm)
        stat = dict(phase=f"hop{i}", matched_edges=jnp.sum(wmask))
        stat["active_edges"] = jnp.sum(
            (src_val if mode == MODE_STATIC else src_val.sum(
                axis=tuple(range(1, src_val.ndim)))) > 0)
        stats.append(stat)

    return SegmentResult(arrivals_e, arrivals_v, stats, mch_v)


# =========================================================================
# plan execution (split-point plans, Sec. 4.3)
# =========================================================================
@dataclasses.dataclass
class ExecOutput:
    total: jnp.ndarray                 # scalar (static/interval) or [B] (bucket)
    per_vertex: Optional[jnp.ndarray]  # aggregation output ([V] / [V,B])
    minmax: Optional[jnp.ndarray]
    stats: List[dict]


def execute_plan_traced(
    gdev: dict,
    qry: Q.PathQuery,
    split: int,
    mode: int,
    n_buckets: int,
    params,
    bedges,
    segment_runner=None,
    delta=None,
):
    """Traceable plan execution.  All query structure is Python-static.

    ``segment_runner`` (defaults to the dense ``run_segment``) lets other
    executors reuse the split/join skeleton: it must return a SegmentResult
    whose arrivals live in GLOBAL vertex/traversal-edge space.
    ``delta`` only parameterises the DEFAULT dense runner.
    """
    with SS.bucket_scope(bedges):
        return _execute_plan_inner(gdev, qry, split, mode, n_buckets, params,
                                   segment_runner, delta=delta)


def _pbases(qry: Q.PathQuery):
    """Parameter-row offsets per predicate (matching query_params order)."""
    pv, pe = [], []
    off = 0
    for v in qry.v_preds:
        pv.append(off)
        off += len(v.clauses)
    for e in qry.e_preds:
        pe.append(off)
        off += len(e.clauses)
    return pv, pe


def _execute_plan_inner(gdev, qry, split, mode, n_buckets, params,
                        segment_runner=None, delta=None):
    n = qry.n_vertices
    assert 0 <= split < n
    pv, pe = _pbases(qry)
    bedges = SS.current_bedges()
    runner = segment_runner
    if runner is None:
        def runner(*a, **kw):
            return run_segment(gdev, *a, delta=delta, **kw)

    want_agg = qry.agg_op != Q.AGG_NONE
    want_minmax = qry.agg_op in (Q.AGG_MIN, Q.AGG_MAX)
    if want_agg:
        assert split == 0, "aggregate queries group by the first vertex → split=0"

    rev = qry.reversed()

    # ---- left segment: v0 .. v_split (forward)
    left = None
    if split > 0:
        left = runner(
            qry.v_preds[: split + 1], qry.e_preds[:split], params,
            pv[: split + 1], pe[:split], mode, n_buckets, backward=False,
        )

    # ---- right segment: v_{n-1} .. v_split (reversed)
    right = None
    n_right_hops = (n - 1) - split
    if n_right_hops > 0:
        # params rows were packed for the ORIGINAL query; map them:
        # rev.v_preds[i] == qry.v_preds[n-1-i]; rev.e_preds[j] == qry.e_preds[n-2-j]
        rpv_orig = [pv[n - 1 - i] for i in range(n)]
        rpe_orig = [pe[n - 2 - j] for j in range(n - 1)]
        right = runner(
            rev.v_preds[: n_right_hops + 1], rev.e_preds[:n_right_hops],
            params, rpv_orig[: n_right_hops + 1], rpe_orig[:n_right_hops],
            mode, n_buckets, backward=True,
            with_minmax=want_minmax,
            minmax_op=qry.agg_op,
            minmax_col=(gdev["vprops"].get(qry.agg_key) if want_minmax else None),
        )

    stats = (left.stats if left else []) + (right.stats if right else [])
    return _join(gdev, qry, split, mode, n_buckets, params, pv[split], bedges,
                 left, right, stats)


@scope("join")
def _join(gdev, qry, split, mode, n_buckets, params, pbase, bedges, left,
          right, stats) -> ExecOutput:
    """The join at v_split: its vertex predicate, then the product of the
    two segments' arrivals (or the ETR-at-join contraction)."""
    n = qry.n_vertices
    want_agg = qry.agg_op != Q.AGG_NONE
    want_minmax = qry.agg_op in (Q.AGG_MIN, Q.AGG_MAX)
    vm, vv = SS.vertex_predicate(gdev, qry.v_preds[split], params, pbase, mode,
                                 bedges)
    etr_at_join = split > 0 and split < n - 1 and qry.e_preds[split].etr_op != -1

    def vertex_apply(av):
        return SS.apply_validity(av, vm, vv, mode)

    if n == 1:  # degenerate single-vertex query
        st = SS.init_state(vm, vv, mode, n_buckets)
        total = SS.state_total(st, mode)
        pv = mm = None
        if want_agg:
            pv = st if mode != MODE_INTERVAL else SS.cells_to_buckets(st)
        if want_minmax:
            vals0, _ = gdev["vprops"][qry.agg_key]
            mm = SS.minmax_seed(st, vals0, qry.agg_op, mode)
        return ExecOutput(total, pv, mm, stats)

    if not etr_at_join:
        if left is None:
            Rv = vertex_apply(right.arrivals_v)
            if want_agg:
                per_vertex = Rv if mode != MODE_INTERVAL else SS.cells_to_buckets(Rv)
                total = SS.state_total(Rv, mode)
                mm = None
                if want_minmax:
                    mm = jnp.where(SS.state_alive(Rv, mode), right.minmax_v,
                                   SS.minmax_neutral(qry.agg_op))
                return ExecOutput(total, per_vertex, mm, stats)
            total = SS.state_total(Rv, mode)
            return ExecOutput(total, None, None, stats)
        if right is None:
            Lv = vertex_apply(left.arrivals_v)
            return ExecOutput(SS.state_total(Lv, mode), None, None, stats)
        # both sides present, plain product join
        Lv = vertex_apply(left.arrivals_v)
        Rv = right.arrivals_v
        if mode == MODE_STATIC:
            total = jnp.sum(Lv * Rv)
        elif mode == MODE_BUCKET:
            total = jnp.sum(Lv * Rv, axis=0)
        else:
            total = jnp.sum(SS.join_interval_counts(Lv, Rv))
        return ExecOutput(total, None, None, stats)

    # ---- ETR-at-join: weight right final edges by left arrivals via ranks
    op = qry.e_preds[split].etr_op
    W = SS.etr_weighted(gdev, left.arrivals_e, op, backward=False, use_arr=True)
    # apply v_split predicate at the join vertex of each right edge
    if mode == MODE_STATIC:
        w_v = vm[gdev["t_dst"]].astype(jnp.float32)
        total = jnp.sum(W * right.arrivals_e * w_v)
    elif mode == MODE_BUCKET:
        mk = (vm[:, None] & vv).astype(jnp.float32)[gdev["t_dst"]]
        total = jnp.sum(W * right.arrivals_e * mk, axis=0)
    else:
        Wc = SS.apply_validity(W, vm[gdev["t_dst"]], vv[gdev["t_dst"]], mode)
        total = jnp.sum(SS.join_interval_counts_edges(Wc, right.arrivals_e))
    return ExecOutput(total, None, None, stats)


# =========================================================================
# public API with jit cache
# =========================================================================
_JIT_CACHE: Dict[tuple, callable] = {}

def _prepare_gdev(graph: TemporalGraph) -> dict:
    g = dict(graph.device_arrays())
    # edge property columns gathered into traversal space (2E), lazily cached
    if "eprops_t" not in g:
        t_eid = np.asarray(graph.traversal["t_eid"])
        g["eprops_t"] = {
            k: (jnp.asarray(c.vals[t_eid]), jnp.asarray(c.life[t_eid]))
            for k, c in graph.eprops.items()
        }
        graph._device_cache = g
    return g


def execute(
    graph: TemporalGraph,
    qry: Q.PathQuery,
    split: Optional[int] = None,
    mode: int = MODE_STATIC,
    n_buckets: int = 16,
    sliced: Optional[bool] = None,
) -> ExecOutput:
    """Execute a path query with the given plan (split point).

    split=None defaults to left-to-right (split = n-1) for plain queries and
    right-to-left (split = 0) for aggregates.  ``sliced`` selects the
    type-sliced optimised path (engine_sliced.py); None = auto.  For the
    partition-sharded distributed path use ``engine_partitioned.execute``.
    """
    if split is None:
        split = 0 if qry.agg_op != Q.AGG_NONE else qry.n_vertices - 1
    gdev = _prepare_gdev(graph)
    bedges = jnp.asarray(
        iv.bucket_edges(graph.lifespan[0], graph.lifespan[1], n_buckets)
    )
    from . import engine_sliced as ES

    use_sliced = sliced
    if use_sliced is None:
        use_sliced = ES.sliceable(qry)
    if use_sliced and not ES.sliceable(qry):
        raise ValueError("query not sliceable (wildcard vertex type)")
    key = (id(graph), qry.shape_key(), split, mode, n_buckets,
           bool(use_sliced))
    fn = _JIT_CACHE.get(key)
    if fn is None:
        if use_sliced:
            sb = ES.SliceBounds.from_graph(graph)

            def traced(gd, params, be):
                out = ES.execute_plan_sliced(gd, qry, split, mode, n_buckets,
                                             params, be, sb)
                return out.total, out.per_vertex, out.minmax, []
        else:
            def traced(gd, params, be):
                out = execute_plan_traced(gd, qry, split, mode, n_buckets,
                                          params, be)
                return (
                    out.total,
                    out.per_vertex,
                    out.minmax,
                    [{k: v for k, v in s.items() if not isinstance(v, str)}
                     for s in out.stats],
                )

        fn = jax.jit(traced)
        _JIT_CACHE[key] = fn
    params = jnp.asarray(Q.query_params(qry))
    total, per_vertex, minmax, stats_vals = fn(gdev, params, bedges)
    if use_sliced and per_vertex is not None:
        # sliced aggregates are on the first-vertex type slice; re-embed
        lo, hi = ES.SliceBounds.from_graph(graph).v[qry.v_preds[0].vtype]
        full_shape = (graph.n_vertices,) + tuple(np.asarray(per_vertex).shape[1:])
        pv = jnp.zeros(full_shape, per_vertex.dtype).at[lo:hi].set(per_vertex)
        per_vertex = pv
    return ExecOutput(total, per_vertex, minmax, stats_vals)


def count_results(graph, qry, **kw) -> float:
    out = execute(graph, qry, **kw)
    t = np.asarray(out.total)
    return float(t.sum()) if t.ndim else float(t)


_MODE_NAMES = {MODE_STATIC: "static", MODE_BUCKET: "bucket",
               MODE_INTERVAL: "interval"}
_AGG_NAMES = {Q.AGG_NONE: "paths", Q.AGG_COUNT: "count", Q.AGG_MIN: "min",
              Q.AGG_MAX: "max"}


def program_name(engine: str, qry: Q.PathQuery, split: int, mode: int) -> str:
    """Stable name of one batched device program: engine, temporal mode,
    hops, aggregate, split, ETR hops, and a CRC-32 of the query shape (the
    same in every process, unlike ``hash``).  ``jax.jit`` names the program
    ``jit_<name>``, so a device trace tells the templates apart."""
    etr = "_etr" if any(e.etr_op != -1 for e in qry.e_preds) else ""
    crc = zlib.crc32(repr(qry.shape_key()).encode()) & 0xFFFFFFFF
    return (f"{engine}_{_MODE_NAMES[mode]}_h{len(qry.e_preds)}_"
            f"{_AGG_NAMES[qry.agg_op]}_s{split}{etr}_{crc:08x}")


def check_batch_shape(queries: Sequence[Q.PathQuery]) -> tuple:
    """Validate that a batch shares one template shape; returns the key."""
    assert queries, "empty batch"
    shape0 = queries[0].shape_key()
    for q in queries[1:]:
        if q.shape_key() != shape0:
            raise ValueError("batched queries must share a template shape")
    return shape0


def batch_executable(
    graph: TemporalGraph,
    qry: Q.PathQuery,
    split: Optional[int] = None,
    mode: int = MODE_STATIC,
    n_buckets: int = 16,
    sliced: Optional[bool] = None,
):
    """Compiled batched entry for one query shape (the serving runtime's
    executable unit).

    Returns ``run(params)`` where ``params`` is the stacked parameter tensor
    int32[B, n_clauses, 3] of same-shape instances; ``run`` yields an
    ``ExecOutput`` whose every field carries a leading query axis.  The jitted
    callable is cached per (graph, shape, plan) and retraces only on a new
    batch size B — callers that pad B to size buckets (serving/compile.py)
    re-trace a bounded number of times, then never again.
    """
    if split is None:
        split = 0 if qry.agg_op != Q.AGG_NONE else qry.n_vertices - 1
    gdev = _prepare_gdev(graph)
    bedges = jnp.asarray(
        iv.bucket_edges(graph.lifespan[0], graph.lifespan[1], n_buckets)
    )
    from . import engine_sliced as ES

    use_sliced = ES.sliceable(qry) if sliced is None else sliced
    if use_sliced and not ES.sliceable(qry):
        raise ValueError("query not sliceable (wildcard vertex type)")
    key = ("batch", id(graph), qry.shape_key(), split, mode, n_buckets,
           bool(use_sliced))
    fn = _JIT_CACHE.get(key)
    if fn is None:
        if use_sliced:
            sb = ES.SliceBounds.from_graph(graph)

            def one(gd, params, be):
                out = ES.execute_plan_sliced(gd, qry, split, mode, n_buckets,
                                             params, be, sb)
                return out.total, out.per_vertex, out.minmax
        else:
            def one(gd, params, be):
                out = execute_plan_traced(gd, qry, split, mode, n_buckets,
                                          params, be)
                return out.total, out.per_vertex, out.minmax

        one.__name__ = program_name("sliced" if use_sliced else "dense", qry,
                                    split, mode)
        fn = jax.jit(jax.vmap(one, in_axes=(None, 0, None)))
        _JIT_CACHE[key] = fn

    embed = None
    if use_sliced and qry.agg_op != Q.AGG_NONE:
        embed = ES.SliceBounds.from_graph(graph).v[qry.v_preds[0].vtype]
    V = graph.n_vertices

    def run(params) -> ExecOutput:
        total, per_vertex, minmax = fn(gdev, jnp.asarray(params), bedges)
        if embed is not None and per_vertex is not None:
            # sliced aggregates live on the first-vertex type slice; re-embed
            lo, hi = embed
            full = jnp.zeros((per_vertex.shape[0], V) + per_vertex.shape[2:],
                             per_vertex.dtype)
            per_vertex = full.at[:, lo:hi].set(per_vertex)
        return ExecOutput(total, per_vertex, minmax, [])

    run.fn = fn   # the jitted program: fn(gdev, params, bedges)
    return run


def batch_executable_delta(
    graph: TemporalGraph,
    qry: Q.PathQuery,
    split: Optional[int] = None,
    mode: int = MODE_STATIC,
    n_buckets: int = 16,
):
    """Base+delta twin of ``batch_executable`` for live-graph serving.

    ``graph`` is the COMPACTED BASE; the returned ``run(params, delta)``
    additionally takes a ``graphdata.ingest.DeltaSpec.device()`` dict and
    answers as if the delta edges were part of the graph — bit-identical to
    ``batch_executable`` on the merged epoch graph (tests/test_ingest.py).

    The jit cache key deliberately EXCLUDES the delta: one cached callable
    serves every epoch of a compaction window, retracing only when the
    delta outgrows its pow-2 padded capacity.  That is the executable-cache
    half of delta-aware invalidation — epochs that only append edges keep
    every compiled executable warm.

    ETR hops read whole-graph rank tables, so queries containing them are
    refused (the scheduler serves those from the merged epoch graph).
    """
    if any(e.etr_op != -1 for e in qry.e_preds):
        raise ValueError("ETR hops need global rank tables — not delta-"
                         "executable; serve from the merged epoch graph")
    if split is None:
        split = 0 if qry.agg_op != Q.AGG_NONE else qry.n_vertices - 1
    gdev = _prepare_gdev(graph)
    bedges = jnp.asarray(
        iv.bucket_edges(graph.lifespan[0], graph.lifespan[1], n_buckets)
    )
    key = ("batch_delta", id(graph), qry.shape_key(), split, mode, n_buckets)
    fn = _JIT_CACHE.get(key)
    if fn is None:
        def one(gd, params, be, delta):
            out = execute_plan_traced(gd, qry, split, mode, n_buckets,
                                      params, be, delta=delta)
            return out.total, out.per_vertex, out.minmax

        one.__name__ = program_name("dense_delta", qry, split, mode)
        fn = jax.jit(jax.vmap(one, in_axes=(None, 0, None, None)))
        _JIT_CACHE[key] = fn

    def run(params, delta) -> ExecOutput:
        total, per_vertex, minmax = fn(gdev, jnp.asarray(params), bedges,
                                       delta)
        return ExecOutput(total, per_vertex, minmax, [])

    return run


def execute_batch_out(
    graph: TemporalGraph,
    queries: Sequence[Q.PathQuery],
    split: Optional[int] = None,
    mode: int = MODE_STATIC,
    n_buckets: int = 16,
    sliced: Optional[bool] = None,
) -> ExecOutput:
    """Batched execution of same-shape instances; full ExecOutput with a
    leading query axis on every field (aggregates included)."""
    check_batch_shape(queries)
    run = batch_executable(graph, queries[0], split, mode, n_buckets, sliced)
    params = np.stack([Q.query_params(q) for q in queries])
    return run(params)


def execute_batch(
    graph: TemporalGraph,
    queries: Sequence[Q.PathQuery],
    split: Optional[int] = None,
    mode: int = MODE_STATIC,
    n_buckets: int = 16,
    sliced: Optional[bool] = None,
) -> np.ndarray:
    """Batched execution of query instances sharing one template shape.

    Queries must share ``shape_key()`` (same predicates/ops/hops — only the
    parameter values differ, e.g. the 100 LDBC instances of one template).
    The executable is vmapped over the packed parameter tensor, so a whole
    template batch costs one traversal sweep per hop — the serving-throughput
    mode of the engine (beyond-paper; see DESIGN.md §2 query-as-data).

    Returns totals [n_queries] (static/interval) or [n_queries, B] (bucket).
    For aggregates / per-vertex outputs use ``execute_batch_out``.
    """
    return np.asarray(
        execute_batch_out(graph, queries, split, mode, n_buckets,
                          sliced).total
    )
