"""Type-sliced plan execution — the §Perf-optimised engine path.

The paper's type-based partitioning (Sec. 4.4.1) lets a superstep skip every
partition whose vertex type cannot match.  In tensor form: vertices are
type-major and traversal edges are arrival-sorted, so *the traversal edges
arriving at one vertex type are one contiguous slice* and a typed hop only
has to touch that slice.  Slice bounds are host-known per graph, hence
compile-time constants; everything else (predicate eval, delivery, ETR rank
prefix sums) operates on the slices unchanged.

Work per hop drops from O(2E) to O(arrivals(σ_{i+1})) and the init from O(V)
to O(|V_σ0|) — this is what makes split-point plans differ in cost and what
the cost model's extent terms (planner.py) measure.

Requires: every vertex predicate carries a type (the LDBC workload does).
Falls back to the dense engine otherwise (engine.execute handles routing).

Layering: this is the SLICED executor of the three-layer stack (superstep
core → dense / sliced / partitioned executors); all hop primitives come from
``superstep.py`` — only the slice bookkeeping lives here.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax.numpy as jnp

from ..obs.trace import scope
from . import query as Q
from . import superstep as SS
from .engine import ExecOutput, _pbases
from .graph import TemporalGraph
from .superstep import MODE_BUCKET, MODE_INTERVAL, MODE_STATIC


@dataclasses.dataclass(frozen=True)
class SliceBounds:
    """Host-side static slice bounds for one graph."""
    v: Tuple[Tuple[int, int], ...]   # per type: [vlo, vhi)
    e: Tuple[Tuple[int, int], ...]   # per type: arrival-edge slice [elo, ehi)

    @staticmethod
    def from_graph(g: TemporalGraph) -> "SliceBounds":
        tr = g.type_ranges
        ptr = g.traversal["arr_ptr"]
        v = tuple((int(a), int(b)) for a, b in tr)
        e = tuple((int(ptr[a]), int(ptr[b])) for a, b in tr)
        return SliceBounds(v, e)


@scope("vertex_pred")
def _vertex_eval_sliced(gdev, vp, params, pbase, mode, bedges, vb):
    lo, hi = vb
    props = {k: (v[0][lo:hi], v[1][lo:hi]) for k, v in gdev["vprops"].items()}
    return SS.eval_predicate(
        props, gdev["v_type"][lo:hi], gdev["v_life"][lo:hi], vp.vtype,
        vp.clauses, params, pbase, mode, bedges,
    )


@scope("edge_pred")
def _edge_eval_sliced(gdev, ep, params, pbase, mode, bedges, eb):
    lo, hi = eb
    eprops = {k: (v[0][lo:hi], v[1][lo:hi]) for k, v in gdev["eprops_t"].items()}
    t_life = gdev["t_life"][lo:hi]
    match, validity = SS.eval_predicate(
        eprops, gdev["t_type"][lo:hi], t_life, ep.etype, ep.clauses,
        params, pbase, mode, bedges,
    )
    isfwd = gdev["t_isfwd"][lo:hi]
    if ep.direction == Q.DIR_OUT:
        dmask = isfwd == 1
    elif ep.direction == Q.DIR_IN:
        dmask = isfwd == 0
    else:
        dmask = jnp.ones_like(isfwd, bool)
    return (match & dmask), validity


@scope("etr_prefix")
def _etr_weighted_sliced(gdev, cnt_prev, op, backward, use_arr,
                         prev_eb, cur_eb, prev_vb):
    """ETR prefix over the previous arrival slice, gathered for the current
    slice's edges.  cnt_prev lives on [prev_eb), ranks are slice-invariant."""
    alpha, terms = SS.ETR_SPECS[(op, backward)]
    plo, phi = prev_eb
    clo, chi = cur_eb
    vlo, _ = prev_vb
    perm_s = gdev["etr_perm_start"][plo:phi] - plo
    perm_e = gdev["etr_perm_end"][plo:phi] - plo
    ranks = (gdev["etr_arr_ranks"] if use_arr else gdev["etr_dep_ranks"])[:, clo:chi]
    ptr = gdev["arr_ptr"]
    segv = (gdev["t_dst"] if use_arr else gdev["t_src"])[clo:chi]

    trailing = cnt_prev.shape[1:]
    zero = jnp.zeros((1,) + trailing, cnt_prev.dtype)
    S_s = jnp.concatenate([zero, jnp.cumsum(cnt_prev[perm_s], axis=0)], axis=0)
    need_end = any(t == 3 for _, t in terms)
    S_e = (jnp.concatenate([zero, jnp.cumsum(cnt_prev[perm_e], axis=0)], axis=0)
           if need_end else None)
    nmax = phi - plo
    base_pos = jnp.clip(ptr[segv] - plo, 0, nmax)
    end_pos = jnp.clip(ptr[segv + 1] - plo, 0, nmax)
    # edges whose source is outside the previous type slice contribute 0
    in_range = (ptr[segv] >= plo) & (ptr[segv + 1] <= phi)
    out = 0.0
    base_s = S_s[base_pos]
    if alpha:
        out = alpha * (S_s[end_pos] - base_s)
    for sign, term in terms:
        S = S_e if term == 3 else S_s
        base = S_e[base_pos] if term == 3 else base_s
        pos = jnp.clip(base_pos + ranks[term], 0, nmax)
        out = out + sign * (S[pos] - base)
    shape_mask = in_range
    for _ in trailing:
        shape_mask = shape_mask[..., None]
    return out * shape_mask.astype(cnt_prev.dtype)


@dataclasses.dataclass
class _SegResult:
    arrivals_e: Optional[jnp.ndarray]   # on the final arrival slice
    arrivals_v: Optional[jnp.ndarray]   # [vhi-vlo, *TS] of final vertex type
    final_eb: Tuple[int, int]
    final_vb: Tuple[int, int]


def _run_segment_sliced(gdev, v_preds, e_preds, params, pv, pe, mode,
                        n_buckets, backward, sb: SliceBounds):
    bedges = SS.current_bedges()
    vb0 = sb.v[v_preds[0].vtype]
    vm, vv = _vertex_eval_sliced(gdev, v_preds[0], params, pv[0], mode, bedges, vb0)
    state_v = SS.init_state(vm, vv, mode, n_buckets)   # on slice of type σ0

    arrivals_e = None
    arrivals_v = None
    prev_raw = None
    prev_eb = None
    cur_vb = vb0
    for i, ep in enumerate(e_preds):
        nxt_vb = sb.v[v_preds[i + 1].vtype]
        cur_eb = sb.e[v_preds[i + 1].vtype]     # edges arriving at next type
        wmask, evalid = _edge_eval_sliced(gdev, ep, params, pe[i], mode,
                                          bedges, cur_eb)
        if i > 0:
            vm, vv = _vertex_eval_sliced(gdev, v_preds[i], params, pv[i], mode,
                                         bedges, cur_vb)
        lo, hi = cur_eb
        vlo, vhi = cur_vb
        src = gdev["t_src"][lo:hi]
        src_local = jnp.clip(src - vlo, 0, vhi - vlo - 1)
        src_in = (src >= vlo) & (src < vhi)
        if ep.etr_op != -1:
            src_cnt = _etr_weighted_sliced(gdev, prev_raw, ep.etr_op, backward,
                                           False, prev_eb, cur_eb, cur_vb)
            with scope("src_gather"):
                if mode == MODE_STATIC:
                    src_val = src_cnt * (vm[src_local] & src_in).astype(jnp.float32)
                elif mode == MODE_BUCKET:
                    mk = (vm[:, None] & vv)
                    src_val = src_cnt * (mk[src_local] & src_in[:, None]).astype(jnp.float32)
                else:
                    src_val = SS.apply_validity(src_cnt, vm[src_local] & src_in,
                                                vv[src_local], mode)
        else:
            if i == 0:
                sv = state_v
            else:
                sv = SS.apply_validity(arrivals_v, vm, vv, mode)
            with scope("src_gather"):
                gathered = sv[src_local]
            m = src_in
            for _ in sv.shape[1:]:
                m = m[..., None]
            src_val = gathered * m.astype(sv.dtype)
        if mode == MODE_STATIC:
            cnt_e = src_val * wmask.astype(jnp.float32)
        elif mode == MODE_BUCKET:
            cnt_e = src_val * (wmask[:, None] & evalid).astype(jnp.float32)
        else:
            cnt_e = SS.apply_validity(src_val, wmask, evalid, mode)
        nvlo, nvhi = nxt_vb
        seg = gdev["t_dst"][lo:hi] - nvlo
        # the slice's CSR offsets, rebased: arr_ptr[nvlo] == lo
        ptr = gdev["arr_ptr"][nvlo:nvhi + 1] - lo
        arrivals_v = SS.deliver(cnt_e, seg, nvhi - nvlo, ptr=ptr)
        arrivals_e = cnt_e
        prev_raw = cnt_e
        prev_eb = cur_eb
        cur_vb = nxt_vb
    return _SegResult(arrivals_e, arrivals_v, prev_eb or sb.e[v_preds[0].vtype],
                      cur_vb)


def execute_plan_sliced(gdev, qry: Q.PathQuery, split: int, mode: int,
                        n_buckets: int, params, bedges, sb: SliceBounds):
    """Sliced twin of engine._execute_plan_inner (counts + count-aggregates)."""
    with SS.bucket_scope(bedges):
        return _inner(gdev, qry, split, mode, n_buckets, params, sb)


def _zero_output(qry, mode, n_buckets, sb, want_agg):
    """Static early-out when any hop's type slice is empty (no such
    vertices exist → zero matches, trivially)."""
    if mode == MODE_BUCKET:
        total = jnp.zeros((n_buckets,), jnp.float32)
    else:
        total = jnp.zeros((), jnp.float32)
    pv = None
    if want_agg:
        lo, hi = sb.v[qry.v_preds[0].vtype]
        shape = (hi - lo,) if mode == MODE_STATIC else (hi - lo, n_buckets)
        pv = jnp.zeros(shape, jnp.float32)
    return ExecOutput(total, pv, None, [])


def _inner(gdev, qry, split, mode, n_buckets, params, sb):
    n = qry.n_vertices
    pv, pe = _pbases(qry)
    bedges = SS.current_bedges()
    want_agg = qry.agg_op != Q.AGG_NONE
    if any(sb.v[v.vtype][1] <= sb.v[v.vtype][0] for v in qry.v_preds):
        return _zero_output(qry, mode, n_buckets, sb, want_agg)
    # arrival types of this plan: forward segment arrives at v_1..v_split,
    # reversed segment arrives at v_{n-2}..v_split
    arrival_preds = list(qry.v_preds[1: split + 1]) + list(qry.v_preds[split: n - 1])
    if any(sb.e[v.vtype][1] <= sb.e[v.vtype][0] for v in arrival_preds):
        return _zero_output(qry, mode, n_buckets, sb, want_agg)
    if want_agg:
        assert qry.agg_op == Q.AGG_COUNT, "sliced path: count aggregates"
        assert split == 0
    rev = qry.reversed()

    left = None
    if split > 0:
        left = _run_segment_sliced(gdev, qry.v_preds[: split + 1],
                                   qry.e_preds[:split], params,
                                   pv[: split + 1], pe[:split], mode,
                                   n_buckets, False, sb)
    right = None
    m_hops = (n - 1) - split
    if m_hops > 0:
        rpv = [pv[n - 1 - i] for i in range(n)]
        rpe = [pe[n - 2 - j] for j in range(n - 1)]
        right = _run_segment_sliced(gdev, rev.v_preds[: m_hops + 1],
                                    rev.e_preds[:m_hops], params,
                                    rpv[: m_hops + 1], rpe[:m_hops], mode,
                                    n_buckets, True, sb)

    with scope("join"):
        vb = sb.v[qry.v_preds[split].vtype]
        vm, vv = _vertex_eval_sliced(gdev, qry.v_preds[split], params, pv[split],
                                     mode, bedges, vb)
        etr_at_join = 0 < split < n - 1 and qry.e_preds[split].etr_op != -1

        def vapply(av):
            return SS.apply_validity(av, vm, vv, mode)

        if n == 1:
            st = SS.init_state(vm, vv, mode, n_buckets)
            pv = None
            if want_agg:
                pv = st if mode != MODE_INTERVAL else SS.cells_to_buckets(st)
            return ExecOutput(SS.state_total(st, mode), pv, None, [])

        if not etr_at_join:
            if left is None:
                Rv = vapply(right.arrivals_v)
                if want_agg:
                    total = SS.state_total(Rv, mode)
                    # interval cells flatten to per-bucket series, as dense does
                    pv = Rv if mode != MODE_INTERVAL else SS.cells_to_buckets(Rv)
                    return ExecOutput(total, pv, None, [])
                return ExecOutput(SS.state_total(Rv, mode), None, None, [])
            if right is None:
                Lv = vapply(left.arrivals_v)
                return ExecOutput(SS.state_total(Lv, mode), None, None, [])
            Lv = vapply(left.arrivals_v)
            Rv = right.arrivals_v
            if mode == MODE_STATIC:
                total = jnp.sum(Lv * Rv)
            elif mode == MODE_BUCKET:
                total = jnp.sum(Lv * Rv, axis=0)
            else:
                total = jnp.sum(SS.join_interval_counts(Lv, Rv))
            return ExecOutput(total, None, None, [])

        # ETR at join: left/right final arrivals share the split-type edge slice
        op = qry.e_preds[split].etr_op
        eb = sb.e[qry.v_preds[split].vtype]
        W = _etr_weighted_sliced(gdev, left.arrivals_e, op, False, True,
                                 eb, eb, vb)
        lo, hi = eb
        vlo, _ = vb
        dst_local = gdev["t_dst"][lo:hi] - vlo
        if mode == MODE_STATIC:
            w_v = vm[dst_local].astype(jnp.float32)
            total = jnp.sum(W * right.arrivals_e * w_v)
        elif mode == MODE_BUCKET:
            mk = (vm[:, None] & vv).astype(jnp.float32)[dst_local]
            total = jnp.sum(W * right.arrivals_e * mk, axis=0)
        else:
            Wc = SS.apply_validity(W, vm[dst_local], vv[dst_local], mode)
            total = jnp.sum(SS.join_interval_counts_edges(Wc, right.arrivals_e))
        return ExecOutput(total, None, None, [])


def sliceable(qry: Q.PathQuery) -> bool:
    return all(v.vtype >= 0 for v in qry.v_preds) and (
        qry.agg_op in (Q.AGG_NONE, Q.AGG_COUNT))
