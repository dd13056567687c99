"""Partition-sharded superstep execution — the DISTRIBUTED executor.

This is the paper's actual execution model (Sec. 4): the graph is split by
the two-level partitioner (``graphdata.partitioner``), each worker owns the
traversal edges *arriving* at its vertices, a superstep is

  local compute   per worker: gather boundary state for its halo sources,
                  apply the edge predicate, and DELIVER locally via a
                  per-worker sorted segment-sum (no cross-worker writes);
  exchange        between supersteps: a point-to-point ragged all-to-all
                  (``superstep.p2p_exchange`` over the partitioner's lane
                  tables) delivers each worker exactly the ghost entries its
                  halo names — only boundary state moves, there is no global
                  [V]-sized buffer and no psum reduction per hop.

State lives OWNER-LOCAL throughout a segment: per-worker [W, Vmax, *TS]
vertex state and [W, Emax, *TS] edge counts.  Global views are materialised
once per segment (the plan skeleton joins in global space), not per hop.

Single-device simulation runs the worker axis with ``jax.vmap`` and the
exchange as an axis transpose; with more than one JAX device the WHOLE plan
runs under ``shard_map`` over a ``workers`` mesh axis (one dispatch per
query/batch) and the same exchange moves with one ``lax.all_to_all`` — both
paths are pure data movement over identical tables, hence bit-identical.

Three exchange channels ride the same mechanism:

  plain-hop state    each hop ships the ghost vertices' count state
                     (``PartitionArrays.exchange_volume()`` entries);
  extremum           MIN/MAX aggregates ship the per-vertex extremum channel
                     alongside (same lanes, ±inf fill — ownership is
                     exclusive, so the exchange is a copy, no pmin/pmax);
  ETR rank summaries ETR hops ship only the boundary rank summaries of cut
                     segments (``etr_exchange_volume()`` entries, O(cut
                     edges)): segment owners produce per-edge summaries from
                     SEGMENT-LOCAL prefix tables (``etr_local_summaries``)
                     and route them to the edges' owners.

Semantics: bit-identical to ``engine.execute`` for all three temporal modes
and the FULL query surface — plain counts, COUNT aggregates, MIN/MAX
aggregates and ETR hops.  Every per-edge/per-vertex value equals the dense
engine's because (a) all elementwise primitives come from ``superstep.py``
unchanged, and (b) each vertex's arrival edges live on ONE worker in
canonical order, so per-worker segment reductions reproduce the dense
delivery exactly.

Batched serving (``batch_executable``): the query-batch leading axis is
vmapped INSIDE the shard_map body, so one dispatch runs (batch × workers)
on the device mesh — the scheduler's unit of work on the distributed path.
"""
from __future__ import annotations

import dataclasses
import time
from functools import partial
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from . import intervals as iv
from . import query as Q
from . import superstep as SS
from ..obs.trace import scope
from .engine import (ExecOutput, SegmentResult, _pbases, _prepare_gdev,
                     execute_plan_traced, program_name)
from .graph import TemporalGraph
from .superstep import MODE_BUCKET, MODE_INTERVAL, MODE_STATIC

#: boundary-exchange channels, in reporting order (measure_supersteps,
#: weak_scaling, fit_cost_model all use these indices)
CHANNELS = ("state", "extremum", "etr")


# =========================================================================
# device tables
# =========================================================================
def _prepare_pdev(arrays) -> dict:
    """jnp views of the padded per-worker tables (PartitionArrays)."""
    return dict(
        own_ids=jnp.asarray(arrays.own_ids),
        edge_ids=jnp.asarray(arrays.edge_ids),
        dst_local=jnp.asarray(arrays.dst_local),
        halo_ids=jnp.asarray(arrays.halo_ids),
        src_halo=jnp.asarray(arrays.src_halo),
        halo_own_slot=jnp.asarray(arrays.halo_own_slot),
        xchg_send_slot=jnp.asarray(arrays.xchg_send_slot),
        xchg_recv_slot=jnp.asarray(arrays.xchg_recv_slot),
        etr_perm_local_s=jnp.asarray(arrays.etr_perm_local_s),
        etr_perm_local_e=jnp.asarray(arrays.etr_perm_local_e),
        etr_src_eids=jnp.asarray(arrays.etr_src_eids),
        etr_src_base=jnp.asarray(arrays.etr_src_base),
        etr_src_len=jnp.asarray(arrays.etr_src_len),
        etr_local_slot=jnp.asarray(arrays.etr_local_slot),
        etr_send_slot=jnp.asarray(arrays.etr_send_slot),
        etr_recv_slot=jnp.asarray(arrays.etr_recv_slot),
    )


def _zero_pad_rows(arr):
    """Append one all-zero entity row so pad sentinels gather zeros."""
    return jnp.concatenate(
        [arr, jnp.zeros((1,) + arr.shape[1:], arr.dtype)], axis=0
    )


def _shard_rows(global_arr, ids):
    """Gather global per-entity rows into padded per-worker layout [W, K, ...];
    pad ids point one past the end and read the synthetic zero row."""
    return _zero_pad_rows(global_arr)[ids]


@scope("src_gather")
def _halo_gather(sv_halo, src_halo):
    """Per-edge gather from each worker's halo slice.  A zero sentinel slot
    is appended per worker so ``src_halo`` pads (= Hmax) can never alias a
    real halo vertex, even when a worker's ghost set is empty."""
    sv_halo = jnp.concatenate(
        [sv_halo, jnp.zeros_like(sv_halo[:, :1])], axis=1)
    return jax.vmap(lambda h, s: h[s])(sv_halo, src_halo)


def _scatter_rows(rows_w, ids, n_global, fill=0.0):
    """Per-worker rows back to global [n_global, ...].  Each real entity
    appears in exactly one worker row; pads land on the dropped sentinel
    row.  ``fill`` sets the untouched-entry value (0 for count channels, the
    aggregation-neutral ±inf for extremum channels).  Used ONCE per segment
    to publish the final global views — never for the per-hop exchange."""
    flat_ids = ids.reshape(-1)
    flat = rows_w.reshape((-1,) + rows_w.shape[2:])
    out = jnp.full((n_global + 1,) + rows_w.shape[2:], fill, rows_w.dtype)
    return out.at[flat_ids].set(flat, unique_indices=False)[:n_global]


def _gather_vpred_w(vm, vv, own_ids):
    """Gather a global vertex predicate at owned vertices, flattened over
    [Wl·Vmax] (pad slots read the synthetic zero row → dead state)."""
    Wl, Vmax = own_ids.shape
    vm_w = _shard_rows(vm, own_ids).reshape(Wl * Vmax)
    vv_w = None
    if vv is not None:
        g = _shard_rows(vv, own_ids)
        vv_w = g.reshape((Wl * Vmax,) + g.shape[2:])
    return vm_w, vv_w


# =========================================================================
# the local hop (per worker): p2p exchange → halo gather → edge apply →
# local delivery
# =========================================================================
def _exchange_state(state_w, pdev, axis_name, fill=0.0):
    """The vertex-state boundary exchange: every worker receives its halo
    slice — self-owned entries by local copy, ghost entries point-to-point."""
    h_max = pdev["halo_ids"].shape[1]
    return SS.p2p_exchange(state_w, pdev["halo_own_slot"],
                           pdev["xchg_send_slot"], pdev["xchg_recv_slot"],
                           h_max, axis_name, fill=fill)


def _local_hop_p2p(state_w, wmask, evalid, pdev, mode: int, axis_name,
                   mch_w=None, minmax_op: int = Q.AGG_MIN):
    """One superstep on owner-local state.

    state_w [Wl, Vmax, *TS] is the owned-vertex state; ``wmask``/``evalid``
    are the (replicated) global edge-predicate results, gathered at owned
    edges.  When ``mch_w`` [Wl, Vmax] is given, the extremum channel is
    exchanged and delivered alongside on the same lanes.

    Returns (cnt_w [Wl, Emax, *TS], arrivals_w [Wl, Vmax, *TS], mch or None).
    """
    edge_ids = pdev["edge_ids"]
    Wl, Emax = edge_ids.shape
    v_max = pdev["own_ids"].shape[1]
    halo = _exchange_state(state_w, pdev, axis_name)        # [Wl, Hmax, *TS]
    src_val = _halo_gather(halo, pdev["src_halo"])          # [Wl, Emax, *TS]
    # local edge predicate application (flatten workers: primitives are
    # elementwise over the leading entity axis)
    flat = lambda a: a.reshape((Wl * Emax,) + a.shape[2:])
    wmask_w = _shard_rows(wmask, edge_ids)
    ev_flat = None if evalid is None else flat(_shard_rows(evalid, edge_ids))
    cnt = SS.apply_edge(flat(src_val), flat(wmask_w), ev_flat, mode)
    cnt_w = cnt.reshape((Wl, Emax) + cnt.shape[1:])
    # local delivery: per-worker sorted segment-sum (pad edges hit the trash
    # segment v_max, sliced off)
    arrivals_w = jax.vmap(
        lambda c, d: SS.deliver(c, d, v_max + 1)
    )(cnt_w, pdev["dst_local"])[:, :v_max]
    mch_out = None
    if mch_w is not None:
        neutral = SS.minmax_neutral(minmax_op)
        m_halo = _exchange_state(mch_w, pdev, axis_name, fill=neutral)
        m_src = _halo_gather(m_halo, pdev["src_halo"])
        m_e = SS.minmax_edge(flat(m_src), cnt, minmax_op, mode)
        mch_out = jax.vmap(
            lambda m, d: SS.deliver_extremum(m, d, v_max + 1, minmax_op)
        )(m_e.reshape((Wl, Emax)), pdev["dst_local"])[:, :v_max]
    return cnt_w, arrivals_w, mch_out


# =========================================================================
# ETR hop: per-worker rank-summary production + p2p summary exchange
# =========================================================================
def _ranks_for_produced(gdev, pdev):
    """Gather the global rank tables at each worker's produced edges:
    [W, 4, Smax]; pads read the appended zero row."""
    ranks_t = gdev["etr_dep_ranks"].T                       # [2E, 4]
    return jnp.swapaxes(_shard_rows(ranks_t, pdev["etr_src_eids"]), 1, 2)


def _worker_etr_summaries(cnt_w, perm_ls, perm_le, base, seg_len, ranks,
                          op: int, backward: bool):
    """Single-worker ETR producer: reorder owned prev-hop counts by the
    per-worker (dst, stat) permutations, take segment-local prefix sums, and
    emit the rank summaries for every edge whose source segment it owns."""
    cnt_pad = jnp.concatenate(
        [cnt_w, jnp.zeros((1,) + cnt_w.shape[1:], cnt_w.dtype)], axis=0)
    cps = cnt_pad[perm_ls]
    cpe = cnt_pad[perm_le] if SS.etr_needs_end(op, backward) else None
    return SS.etr_local_summaries(cps, cpe, base, seg_len, ranks, op, backward)


def _etr_produce_w(cnt_prev_w, gdev, pdev, op: int, backward: bool):
    """All workers' rank summaries from their owned prev-hop counts:
    [Wl, Smax, *TS]."""
    ranks_w = _ranks_for_produced(gdev, pdev)
    return jax.vmap(
        lambda c, pls, ple, b, sl, r: _worker_etr_summaries(
            c, pls, ple, b, sl, r, op, backward)
    )(cnt_prev_w, pdev["etr_perm_local_s"], pdev["etr_perm_local_e"],
      pdev["etr_src_base"], pdev["etr_src_len"], ranks_w)


def _exchange_etr(out_w, pdev, axis_name):
    """The ETR boundary exchange: producers route each summary to the edge's
    owner — self-consumed summaries by local copy, boundary summaries (cut
    segments) point-to-point.  Returns the per-owned-edge summary buffer
    [Wl, Emax, *TS]."""
    e_max = pdev["edge_ids"].shape[1]
    return SS.p2p_exchange(out_w, pdev["etr_local_slot"],
                           pdev["etr_send_slot"], pdev["etr_recv_slot"],
                           e_max, axis_name)


def _etr_apply_sources(summ_flat, vm, vv, tsrc_flat, mode: int):
    """Intermediate vertex predicate at the owned edges' source vertices
    (replicated elementwise compute, no exchange)."""
    if mode == MODE_STATIC:
        return summ_flat * vm[tsrc_flat].astype(jnp.float32)
    if mode == MODE_BUCKET:
        return summ_flat * (vm[:, None] & vv)[tsrc_flat].astype(jnp.float32)
    return SS.apply_validity(summ_flat, vm[tsrc_flat], vv[tsrc_flat], mode)


def _etr_hop_p2p(gdev, pdev, cnt_prev_w, vm, vv, wmask, evalid, op: int,
                 backward: bool, mode: int, axis_name):
    """One ETR superstep on owner-local state: produce → exchange →
    consumer edge apply + local delivery."""
    edge_ids = pdev["edge_ids"]
    Wl, Emax = edge_ids.shape
    v_max = pdev["own_ids"].shape[1]
    out_w = _etr_produce_w(cnt_prev_w, gdev, pdev, op, backward)
    summ_w = _exchange_etr(out_w, pdev, axis_name)          # [Wl, Emax, *TS]
    flat = lambda a: a.reshape((Wl * Emax,) + a.shape[2:])
    tsrc_flat = _shard_rows(gdev["t_src"], edge_ids).reshape(-1)
    sv = _etr_apply_sources(flat(summ_w), vm, vv, tsrc_flat, mode)
    ev_flat = None if evalid is None else flat(_shard_rows(evalid, edge_ids))
    cnt = SS.apply_edge(sv, flat(_shard_rows(wmask, edge_ids)), ev_flat, mode)
    cnt_w = cnt.reshape((Wl, Emax) + cnt.shape[1:])
    arrivals_w = jax.vmap(
        lambda c, d: SS.deliver(c, d, v_max + 1)
    )(cnt_w, pdev["dst_local"])[:, :v_max]
    return cnt_w, arrivals_w


# =========================================================================
# segment runner (plugs into engine.execute_plan_traced)
# =========================================================================
def run_segment_partitioned(
    gdev: dict,
    pdev: dict,
    axis_name: Optional[str],
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
) -> SegmentResult:
    """Partitioned twin of engine.run_segment on owner-local state.

    ``axis_name`` names the shard_map mesh axis the worker dimension is
    sharded over (None = single-device vmap simulation).  Per-hop state
    never leaves the workers except through the point-to-point exchange;
    the GLOBAL views the shared plan/join skeleton needs are published once
    at segment end (the only psum on the distributed path)."""
    V = gdev["v_life"].shape[0]
    n2e = gdev["t_dst"].shape[0]
    stats: List[dict] = []
    bedges = SS.current_bedges()
    own_ids = pdev["own_ids"]
    Wl, Vmax = own_ids.shape

    vm, vv = SS.vertex_predicate(gdev, v_preds[0], params, pbases_v[0], mode,
                                 bedges)
    vm_w, vv_w = _gather_vpred_w(vm, vv, own_ids)
    state = SS.init_state(vm_w, vv_w, mode, n_buckets)
    state_w = state.reshape((Wl, Vmax) + state.shape[1:])
    stats.append(dict(phase="init", matched=jnp.sum(vm)))

    mch_w = None   # owner-local extremum channel [Wl, Vmax]
    if with_minmax:
        vals0, _ = minmax_col
        g = _shard_rows(vals0, own_ids)
        mch = SS.minmax_seed(state, g.reshape((Wl * Vmax,) + g.shape[2:]),
                             minmax_op, mode)
        mch_w = mch.reshape(Wl, Vmax)

    cnt_w = None       # owner-local per-edge counts of the last hop
    arrivals_w = None  # owner-local last delivery [Wl, Vmax, *TS]
    for i, ep in enumerate(e_preds):
        wmask, evalid = SS.edge_predicate_weights(
            gdev, ep, params, pbases_e[i], mode, bedges)
        if i > 0:
            vm, vv = SS.vertex_predicate(gdev, v_preds[i], params,
                                         pbases_v[i], mode, bedges)
        if ep.etr_op != -1:
            if with_minmax:
                raise NotImplementedError(
                    "min/max aggregation across ETR hops")
            cnt_w, arrivals_w = _etr_hop_p2p(
                gdev, pdev, cnt_w, vm, vv, wmask, evalid, ep.etr_op,
                backward, mode, axis_name)
        else:
            if i > 0:
                vm_w, vv_w = _gather_vpred_w(vm, vv, own_ids)
                av = arrivals_w.reshape((Wl * Vmax,) + arrivals_w.shape[2:])
                state = SS.apply_validity(av, vm_w, vv_w, mode)
                state_w = state.reshape((Wl, Vmax) + state.shape[1:])
            cnt_w, arrivals_w, mch_w = _local_hop_p2p(
                state_w, wmask, evalid, pdev, mode, axis_name,
                mch_w, minmax_op)
        stats.append(dict(phase=f"hop{i}", matched_edges=jnp.sum(wmask)))

    # publish the segment's GLOBAL views (the skeleton joins in global
    # space); under shard_map the partial scatters combine with one psum
    # (pmin/pmax for the extremum channel) — once per segment, not per hop.
    with scope("exchange"):
        arrivals_e = _scatter_rows(cnt_w, pdev["edge_ids"], n2e)
        arrivals_v = _scatter_rows(arrivals_w, pdev["own_ids"], V)
        mch_g = None
        if mch_w is not None:
            mch_g = _scatter_rows(mch_w, pdev["own_ids"], V,
                                  fill=SS.minmax_neutral(minmax_op))
        if axis_name is not None:
            arrivals_e = jax.lax.psum(arrivals_e, axis_name)
            arrivals_v = jax.lax.psum(arrivals_v, axis_name)
            if mch_g is not None:
                combine = (jax.lax.pmin if minmax_op == Q.AGG_MIN
                           else jax.lax.pmax)
                mch_g = combine(mch_g, axis_name)
    return SegmentResult(arrivals_e, arrivals_v, stats, mch_g)


# =========================================================================
# shard_map wrapper (whole-plan, one dispatch)
# =========================================================================
def _wrap_shard_map(body, n_devices: int):
    """shard_map a whole traced plan ``body(gdev, pdev, params, bedges)``:
    the per-worker tables are sharded over the ``workers`` mesh axis, the
    graph tables/params replicated, the outputs replicated (identical on
    every device after the segment-end psum)."""
    from ..launch.mesh import make_worker_mesh

    mesh = make_worker_mesh(n_devices)
    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(), P("workers"), P(), P()),
        out_specs=P(),
        check_vma=False,
    )


# =========================================================================
# public API
# =========================================================================
_JIT_CACHE: Dict[tuple, callable] = {}


def partition_for(graph: TemporalGraph, n_workers: int,
                  parts_per_type: Optional[int] = None):
    """(Partitioning, PartitionArrays, device tables) for a graph, cached ON
    the graph object (like its device-array cache) so the cache's lifetime —
    and the validity of the per-graph ownership tables — is tied to the
    graph itself."""
    from ..graphdata.partitioner import build_partition_arrays, partition_graph

    ppt = parts_per_type if parts_per_type is not None else max(4, n_workers // 2)
    cache = getattr(graph, "_partition_cache", None)
    if cache is None:
        cache = {}
        graph._partition_cache = cache
    key = (n_workers, ppt)
    hit = cache.get(key)
    if hit is None:
        # an ingestion epoch attaches a partition hint (graphdata/ingest.py)
        # that extends the BASE graph's cached partitioning over the delta
        # instead of re-running BFS growth; None → fresh partition
        part = None
        hint = getattr(graph, "_partition_hint", None)
        if hint is not None:
            part = hint(n_workers, ppt)
        if part is None:
            part = partition_graph(graph, n_workers=n_workers,
                                   parts_per_type=ppt)
        arrays = build_partition_arrays(graph, part)
        hit = (part, arrays, _prepare_pdev(arrays))
        cache[key] = hit
    return hit


def device_tables(graph: TemporalGraph, n_workers: int, n_devices: int = 1,
                  parts_per_type: Optional[int] = None):
    """(graph tables, per-worker tables, PartitionArrays) as the executor
    consumes them.  On one device they are the graph's cached
    device arrays.  Over ``n_devices`` they are placed once where the
    shard_map program reads them — per-worker tables sharded over the
    ``workers`` mesh axis, graph tables replicated on every device — and
    cached on the graph, so no dispatch re-sends them from device 0."""
    gdev = _prepare_gdev(graph)
    _, arrays, pdev = partition_for(graph, n_workers, parts_per_type)
    if n_devices > 1:
        from ..launch.mesh import make_worker_mesh

        cache = getattr(graph, "_placed_tables", None)
        if cache is None:
            cache = {}
            graph._placed_tables = cache
        key = (n_workers, parts_per_type, n_devices)
        if key not in cache:
            mesh = make_worker_mesh(n_devices)
            cache[key] = (
                jax.device_put(gdev, NamedSharding(mesh, P())),
                jax.device_put(pdev, NamedSharding(mesh, P("workers"))))
        gdev, pdev = cache[key]
    return gdev, pdev, arrays


def resolve_n_devices(requested: Optional[bool], n_workers: int) -> int:
    """How many devices to shard the worker axis over (1 = vmap simulation).
    ``requested`` is the user's ``use_shard_map`` tri-state: False forces the
    simulation, None/True shard when devices exist and divide the workers."""
    nd = jax.device_count()
    if requested is False or nd <= 1 or n_workers % nd != 0:
        return 1
    return nd


def _plan_fn(qry, split, mode, n_buckets, n_devices, batched: bool = False):
    """Build the jitted (possibly shard_mapped) plan callable — the ONE
    construction both the sequential ``execute`` and the serving
    ``batch_executable`` entries share.  ``batched`` vmaps the params axis;
    on the sharded path that vmap sits INSIDE the shard_map body, so one
    dispatch runs (batch × workers) on the device mesh."""
    def plan(gd, pd, params, be, axis_name):
        runner = partial(run_segment_partitioned, gd, pd, axis_name)
        out = execute_plan_traced(gd, qry, split, mode, n_buckets, params,
                                  be, segment_runner=runner)
        return out.total, out.per_vertex, out.minmax

    axis = None if n_devices <= 1 else "workers"

    def body(gd, pd, p, be):
        return plan(gd, pd, p, be, axis)

    body.__name__ = program_name("partitioned", qry, split, mode)
    if batched:
        body = jax.vmap(body, in_axes=(None, None, 0, None))
    if n_devices <= 1:
        return jax.jit(body)
    return jax.jit(_wrap_shard_map(body, n_devices))


def execute(
    graph: TemporalGraph,
    qry: Q.PathQuery,
    split: Optional[int] = None,
    mode: int = MODE_STATIC,
    n_buckets: int = 16,
    n_workers: int = 4,
    parts_per_type: Optional[int] = None,
    use_shard_map: Optional[bool] = None,
) -> ExecOutput:
    """Partition-sharded execution; identical results to ``engine.execute``.

    ``n_workers`` selects the two-level partitioning (cached per graph).
    When >1 JAX devices exist and divide ``n_workers``, the whole plan runs
    under shard_map on a ``workers`` device mesh (point-to-point exchange
    between supersteps); otherwise the worker axis is vmapped on one device.
    """
    if split is None:
        split = 0 if qry.agg_op != Q.AGG_NONE else qry.n_vertices - 1
    n_devices = resolve_n_devices(use_shard_map, n_workers)
    gdev, pdev, arrays = device_tables(graph, n_workers, n_devices,
                                       parts_per_type)
    bedges = jnp.asarray(
        iv.bucket_edges(graph.lifespan[0], graph.lifespan[1], n_buckets)
    )
    key = (id(graph), qry.shape_key(), split, mode, n_buckets, n_workers,
           arrays.v_max, n_devices)
    fn = _JIT_CACHE.get(key)
    if fn is None:
        fn = _plan_fn(qry, split, mode, n_buckets, n_devices)
        _JIT_CACHE[key] = fn
    params = jnp.asarray(Q.query_params(qry))
    total, per_vertex, minmax = fn(gdev, pdev, params, bedges)
    return ExecOutput(total, per_vertex, minmax, [])


def count_results(graph, qry, **kw) -> float:
    out = execute(graph, qry, **kw)
    t = np.asarray(out.total)
    return float(t.sum()) if t.ndim else float(t)


def hop_exchange_channels(qry: Q.PathQuery, arrays) -> List[Dict[str, int]]:
    """Structural per-HOP boundary volume per channel on the p2p lanes —
    the CANONICAL statement of what each hop exchanges (the flight
    recorder's per-hop exchange spans report exactly these rows; the
    planner's ``estimate_segment`` channels/m_net terms apply the same rule
    per step).  Mirrors the plan skeleton: aggregates run the reversed
    segment, MIN/MAX ride the extremum channel on every plain hop, ETR hops
    ship only the boundary rank summaries."""
    minmax = qry.agg_op in (Q.AGG_MIN, Q.AGG_MAX)
    rows = []
    for ep in qry.e_preds:
        if ep.etr_op != -1:
            rows.append(dict(state=0, extremum=0,
                             etr=int(arrays.etr_exchange_volume())))
        else:
            v = int(arrays.exchange_volume())
            rows.append(dict(state=v, extremum=v if minmax else 0, etr=0))
    return rows


def query_exchange_volumes(qry: Q.PathQuery, arrays) -> Dict[str, int]:
    """Whole-query boundary volume per channel: the sum of
    ``hop_exchange_channels`` over the query's hops."""
    totals = dict(state=0, extremum=0, etr=0)
    for row in hop_exchange_channels(qry, arrays):
        for ch, v in row.items():
            totals[ch] += v
    return totals


def batch_executable(
    graph: TemporalGraph,
    qry: Q.PathQuery,
    split: Optional[int] = None,
    mode: int = MODE_STATIC,
    n_buckets: int = 16,
    n_workers: int = 4,
    parts_per_type: Optional[int] = None,
    use_shard_map: Optional[bool] = None,
):
    """Compiled batched entry on the DISTRIBUTED path: the whole superstep
    pipeline (p2p halo exchange → local delivery → segment-end publish) runs
    with a query-batch leading axis — one partitioned traversal sweep serves
    the entire same-shape batch.

    Returns ``run(params[B, n_clauses, 3]) -> ExecOutput`` with a leading
    query axis on every field.  With >1 devices dividing ``n_workers`` the
    batch axis is vmapped INSIDE the shard_map body, so ONE dispatch runs
    (batch × workers) on the device mesh; otherwise the worker axis runs in
    the (bit-identical) single-device vmap simulation.
    """
    if split is None:
        split = 0 if qry.agg_op != Q.AGG_NONE else qry.n_vertices - 1
    n_devices = resolve_n_devices(use_shard_map, n_workers)
    gdev, pdev, arrays = device_tables(graph, n_workers, n_devices,
                                       parts_per_type)
    bedges = jnp.asarray(
        iv.bucket_edges(graph.lifespan[0], graph.lifespan[1], n_buckets)
    )
    key = ("batch", id(graph), qry.shape_key(), split, mode, n_buckets,
           n_workers, arrays.v_max, n_devices)
    fn = _JIT_CACHE.get(key)
    if fn is None:
        fn = _plan_fn(qry, split, mode, n_buckets, n_devices, batched=True)
        _JIT_CACHE[key] = fn

    def run(params) -> ExecOutput:
        total, per_vertex, minmax = fn(gdev, pdev, jnp.asarray(params), bedges)
        return ExecOutput(total, per_vertex, minmax, [])

    run.fn = fn   # the jitted program: fn(gdev, pdev, params, bedges)
    return run


def execute_batch_out(
    graph: TemporalGraph,
    queries: Sequence[Q.PathQuery],
    split: Optional[int] = None,
    mode: int = MODE_STATIC,
    n_buckets: int = 16,
    n_workers: int = 4,
    parts_per_type: Optional[int] = None,
    use_shard_map: Optional[bool] = None,
) -> ExecOutput:
    """Batched partitioned execution of same-shape instances."""
    from .engine import check_batch_shape
    check_batch_shape(queries)
    run = batch_executable(graph, queries[0], split, mode, n_buckets,
                           n_workers, parts_per_type, use_shard_map)
    params = np.stack([Q.query_params(q) for q in queries])
    return run(params)


# =========================================================================
# instrumented per-worker superstep timing (weak-scaling benchmark)
# =========================================================================
@dataclasses.dataclass
class SuperstepProfile:
    times_s: np.ndarray            # float64[n_hops, W] — measured local-hop time
    exchange_msgs: np.ndarray      # int64[n_hops] — boundary messages (all channels)
    exchange_channels: np.ndarray  # int64[n_hops, 3] — per CHANNELS breakdown
    total: float                   # query total (sanity cross-check)

    @property
    def makespan_s(self) -> np.ndarray:
        """Per-superstep makespan: the straggler worker's measured time."""
        return self.times_s.max(axis=1)

    @property
    def balance_eff(self) -> float:
        per_worker = self.times_s.sum(axis=0)
        return float(per_worker.mean() / max(per_worker.max(), 1e-12))

    def channel_totals(self) -> Dict[str, int]:
        """Whole-query boundary volume per exchange channel."""
        sums = self.exchange_channels.sum(axis=0)
        return {name: int(sums[i]) for i, name in enumerate(CHANNELS)}


_PROFILE_CACHE: Dict[tuple, dict] = {}


def _profile_fns(qry: Q.PathQuery, mode: int, n_buckets: int, v_max: int,
                 v_preds, e_preds, pv, pe, backward: bool,
                 with_minmax: bool, minmax_op: int) -> dict:
    """Jitted helpers for measure_supersteps, cached per (query shape, mode,
    buckets, padded worker extent) so repeated profiling of one
    template (weak_scaling, fit_cost_model) re-traces nothing.  All graph
    data is passed as arguments; only static query structure is baked in."""
    # shape_key() covers agg_op/agg_key, i.e. the full profiled structure
    key = (qry.shape_key(), mode, n_buckets, v_max)
    fns = _PROFILE_CACHE.get(key)
    if fns is not None:
        return fns

    def vpred(i):
        def f(gd, prm, be):
            with SS.bucket_scope(be):
                return SS.vertex_predicate(gd, v_preds[i], prm, pv[i], mode,
                                           be)
        return jax.jit(f)

    def hop_masks(i):
        def f(gd, prm, be):
            with SS.bucket_scope(be):
                return SS.edge_predicate_weights(gd, e_preds[i], prm,
                                                 pe[i], mode, be)
        return jax.jit(f)

    @jax.jit
    def init_fn(m, v, own, be):
        with SS.bucket_scope(be):
            Wl, Vmax = own.shape
            m_w, v_w = _gather_vpred_w(m, v if v.ndim else None, own)
            st = SS.init_state(m_w, v_w, mode, n_buckets)
            return st.reshape((Wl, Vmax) + st.shape[1:])

    @jax.jit
    def seed_mch(state_w, vals0, own):
        Wl, Vmax = own.shape
        g = _shard_rows(vals0, own)
        st = state_w.reshape((Wl * Vmax,) + state_w.shape[2:])
        mch = SS.minmax_seed(st, g.reshape((Wl * Vmax,) + g.shape[2:]),
                             minmax_op, mode)
        return mch.reshape(Wl, Vmax)

    @jax.jit
    def apply_vv_w(arr_w, m, v, own, be):
        with SS.bucket_scope(be):
            Wl, Vmax = own.shape
            m_w, v_w = _gather_vpred_w(m, v if v.ndim else None, own)
            st = SS.apply_validity(
                arr_w.reshape((Wl * Vmax,) + arr_w.shape[2:]), m_w, v_w, mode)
            return st.reshape((Wl, Vmax) + st.shape[1:])

    # the UNTIMED exchanges: point-to-point lane moves between supersteps
    @jax.jit
    def exchange_state_fn(state_w, pd):
        return _exchange_state(state_w, pd, None)

    @jax.jit
    def exchange_mch_fn(mch_w, pd):
        return _exchange_state(mch_w, pd, None,
                               fill=SS.minmax_neutral(minmax_op))

    # ONE compiled local-hop executable reused for every (hop, worker): each
    # worker's tables arrive with a leading axis of 1 so shapes agree.  The
    # halo buffer arrives pre-exchanged; the TIMED work is the local gather,
    # edge apply and delivery — the per-worker compute a real deployment's
    # straggler/makespan comes from.
    @jax.jit
    def one_worker_hop(halo_1, wm, ev, eids, dloc, shalo, mch_halo, be):
        with SS.bucket_scope(be):
            e_max = eids.shape[1]
            flatten = lambda a: a.reshape((e_max,) + a.shape[2:])
            evf = None if not ev.ndim else flatten(_shard_rows(ev, eids))
            src_val = _halo_gather(halo_1, shalo)
            cnt = SS.apply_edge(flatten(src_val),
                                flatten(_shard_rows(wm, eids)), evf, mode)
            arr = SS.deliver(cnt, dloc[0], v_max + 1)[:v_max]
            if mch_halo.ndim:
                m_src = _halo_gather(mch_halo, shalo)
                m_e = SS.minmax_edge(flatten(m_src), cnt, minmax_op, mode)
                mch = SS.deliver_extremum(m_e, dloc[0], v_max + 1,
                                          minmax_op)[:v_max][None]
            else:
                mch = jnp.zeros((), jnp.float32)
            return cnt[None], arr[None], mch

    # ETR producer body: segment-local prefix tables over the worker's owned
    # prev-hop counts → rank summaries for the edges whose source it owns.
    def etr_produce(i):
        op = e_preds[i].etr_op

        def f(cnt_1, pls, ple, base, slen, ranks, be):
            with SS.bucket_scope(be):
                return _worker_etr_summaries(cnt_1[0], pls[0], ple[0],
                                             base[0], slen[0], ranks[0], op,
                                             backward)[None]
        return jax.jit(f)

    @jax.jit
    def exchange_etr_fn(out_w, pd):
        return _exchange_etr(out_w, pd, None)

    # ETR consumer body: the received summaries are the exchanged state; the
    # local part is source-predicate apply + edge apply + delivery.
    @jax.jit
    def one_worker_etr(summ_1, m, v, tsrc, wm, ev, eids, dloc, be):
        with SS.bucket_scope(be):
            e_max = eids.shape[1]
            flatten = lambda a: a.reshape((e_max,) + a.shape[2:])
            sv = _etr_apply_sources(flatten(summ_1), m,
                                    v if v.ndim else None,
                                    _shard_rows(tsrc, eids).reshape(-1), mode)
            evf = None if not ev.ndim else flatten(_shard_rows(ev, eids))
            cnt = SS.apply_edge(sv, flatten(_shard_rows(wm, eids)), evf, mode)
            arr = SS.deliver(cnt, dloc[0], v_max + 1)[:v_max]
            return cnt[None], arr[None]

    @jax.jit
    def total_fn(arr_w, own, m, v, be):
        with SS.bucket_scope(be):
            V = m.shape[0]
            av = _scatter_rows(arr_w, own, V)
            return SS.state_total(
                SS.apply_validity(av, m, v if v.ndim else None, mode), mode)

    fns = dict(
        vpred=[vpred(i) for i in range(len(v_preds))],
        hop_masks=[hop_masks(i) for i in range(len(e_preds))],
        etr_produce=[etr_produce(i) if ep.etr_op != -1 else None
                     for i, ep in enumerate(e_preds)],
        init_fn=init_fn,
        seed_mch=seed_mch,
        apply_vv_w=apply_vv_w,
        exchange_state_fn=exchange_state_fn,
        exchange_mch_fn=exchange_mch_fn,
        exchange_etr_fn=exchange_etr_fn,
        one_worker_hop=one_worker_hop,
        one_worker_etr=one_worker_etr,
        total_fn=total_fn,
    )
    _PROFILE_CACHE[key] = fns
    return fns


def measure_supersteps(
    graph: TemporalGraph,
    qry: Q.PathQuery,
    n_workers: int = 4,
    mode: int = MODE_STATIC,
    n_buckets: int = 16,
    parts_per_type: Optional[int] = None,
    repeats: int = 2,
    tracer=None,
) -> SuperstepProfile:
    """Measured (not modelled) per-worker superstep times.

    ``tracer`` (an ``obs.trace.Tracer``; None/NULL_TRACER = off) records the
    profile as a span tree — measure_supersteps → superstep (per hop, with
    the per-worker measured times) → exchange (per-channel boundary
    volumes) — the same schema the serving flight recorder emits, so
    trace_report renders profiler runs and served queries alike.

    Plain-count queries profile the left-to-right plan (split = n−1); COUNT
    and MIN/MAX aggregates profile the reversed segment (split = 0, the plan
    aggregates run), with MIN/MAX threading the extremum channel through
    every hop — so all three boundary channels are measurable.  Each
    worker's local compute runs SEPARATELY through one compiled
    single-worker hop function and is timed with block_until_ready; the
    point-to-point exchange (state / extremum / ETR rank-summary lanes) runs
    between timings, untimed, and its per-channel ragged volume is reported
    in ``exchange_channels`` (halo ghosts for state and extremum, boundary
    rank summaries — cut edges — for ETR).
    """
    want_minmax = qry.agg_op in (Q.AGG_MIN, Q.AGG_MAX)
    if want_minmax and any(ep.etr_op != -1 for ep in qry.e_preds):
        # same rejection as every executor: a profile of an unrunnable plan
        # would silently poison the θ_net fit population
        raise NotImplementedError("min/max aggregation across ETR hops")
    backward = qry.agg_op != Q.AGG_NONE
    gdev = _prepare_gdev(graph)
    _, arrays, pdev = partition_for(graph, n_workers, parts_per_type)
    W = arrays.n_workers
    v_max = arrays.v_max
    bedges = jnp.asarray(
        iv.bucket_edges(graph.lifespan[0], graph.lifespan[1], n_buckets)
    )
    params = jnp.asarray(Q.query_params(qry))
    pv, pe = _pbases(qry)
    n = qry.n_vertices
    if backward:
        # the aggregate plan's (reversed) segment, params rows mapped back
        # to the original packing — same mapping as execute_plan_traced
        rev = qry.reversed()
        v_preds, e_preds = rev.v_preds, rev.e_preds
        pv = [pv[n - 1 - i] for i in range(n)]
        pe = [pe[n - 2 - j] for j in range(n - 1)]
    else:
        v_preds, e_preds = qry.v_preds, qry.e_preds
    n_hops = len(e_preds)

    fns = _profile_fns(qry, mode, n_buckets, v_max, v_preds, e_preds, pv, pe,
                       backward, want_minmax, qry.agg_op)
    vpred, hop_masks = fns["vpred"], fns["hop_masks"]
    etr_produce = fns["etr_produce"]
    ranks_w = _ranks_for_produced(gdev, pdev)

    def _timed(fn, *args):
        best, out = np.inf, None
        for _ in range(max(1, repeats)):
            t0 = time.perf_counter()
            out = fn(*args)
            jax.block_until_ready(out)
            best = min(best, time.perf_counter() - t0)
        return best, out

    # ev/vv=None can't cross jit; encode "absent" as a 0-d placeholder.
    nul = jnp.zeros((), jnp.float32)

    times = np.zeros((n_hops, W))
    channels = np.zeros((n_hops, len(CHANNELS)), np.int64)
    n_ghost = int(arrays.n_ghost.sum())
    n_etr_ghost = int(arrays.n_src_ghost.sum())

    vm, vv = vpred[0](gdev, params, bedges)
    vv_arg = nul if vv is None else vv
    state_w = fns["init_fn"](vm, vv_arg, pdev["own_ids"], bedges)
    mch_w = None
    if want_minmax:
        vals0, _ = gdev["vprops"][qry.agg_key]
        mch_w = fns["seed_mch"](state_w, vals0, pdev["own_ids"])
    cnt_w = None
    arrivals_w = None
    for i, ep in enumerate(e_preds):
        wmask, evalid = hop_masks[i](gdev, params, bedges)
        ev_arg = nul if evalid is None else evalid
        if i > 0:
            vm, vv = vpred[i](gdev, params, bedges)
            vv_arg = nul if vv is None else vv
        cnt_rows, arr_rows, mch_rows = [], [], []
        if ep.etr_op != -1:
            # producer half: each owner's summary production over its LOCAL
            # prefix tables is timed as part of that worker's superstep
            summ_rows = []
            for w in range(W):
                t_prod, ow = _timed(
                    etr_produce[i], cnt_w[w: w + 1],
                    pdev["etr_perm_local_s"][w: w + 1],
                    pdev["etr_perm_local_e"][w: w + 1],
                    pdev["etr_src_base"][w: w + 1],
                    pdev["etr_src_len"][w: w + 1],
                    ranks_w[w: w + 1], bedges)
                times[i, w] = t_prod
                summ_rows.append(ow)
            # rank-summary exchange (untimed): only boundary summaries —
            # producer ≠ consumer, O(cut edges) — are cross-partition traffic
            summ_w = fns["exchange_etr_fn"](
                jnp.concatenate(summ_rows, axis=0), pdev)
            channels[i, 2] = n_etr_ghost
            for w in range(W):
                t_best, (cw, aw) = _timed(
                    fns["one_worker_etr"], summ_w[w: w + 1], vm, vv_arg,
                    gdev["t_src"], wmask, ev_arg,
                    pdev["edge_ids"][w: w + 1], pdev["dst_local"][w: w + 1],
                    bedges)
                times[i, w] += t_best
                cnt_rows.append(cw)
                arr_rows.append(aw)
        else:
            if i > 0:
                state_w = fns["apply_vv_w"](arrivals_w, vm, vv_arg,
                                            pdev["own_ids"], bedges)
            # state (+ extremum) exchange (untimed): ghost entries only
            halo_w = fns["exchange_state_fn"](state_w, pdev)
            channels[i, 0] = n_ghost
            mch_halo_w = nul
            if mch_w is not None:
                mch_halo_w = fns["exchange_mch_fn"](mch_w, pdev)
                channels[i, 1] = n_ghost
            for w in range(W):
                mh = mch_halo_w if not mch_halo_w.ndim else \
                    mch_halo_w[w: w + 1]
                t_best, (cw, aw, mw) = _timed(
                    fns["one_worker_hop"], halo_w[w: w + 1], wmask, ev_arg,
                    pdev["edge_ids"][w: w + 1], pdev["dst_local"][w: w + 1],
                    pdev["src_halo"][w: w + 1], mh, bedges)
                times[i, w] = t_best
                cnt_rows.append(cw)
                arr_rows.append(aw)
                mch_rows.append(mw)
            if mch_w is not None:
                mch_w = jnp.concatenate(mch_rows, axis=0)
        cnt_w = jnp.concatenate(cnt_rows, axis=0)
        arrivals_w = jnp.concatenate(arr_rows, axis=0)

    # final join: apply the segment-final vertex predicate, total (sanity)
    vmf, vvf = vpred[len(v_preds) - 1](gdev, params, bedges)
    total = np.asarray(fns["total_fn"](
        arrivals_w, pdev["own_ids"], vmf,
        nul if vvf is None else vvf, bedges))
    profile = SuperstepProfile(times, channels.sum(axis=1), channels,
                               float(total.sum()))
    if tracer is not None and getattr(tracer, "enabled", False):
        root = tracer.start("measure_supersteps", n_workers=W,
                            n_hops=n_hops, mode=mode,
                            backward=backward)
        for i in range(n_hops):
            ss = tracer.start(
                "superstep", parent=root, hop=i,
                measured_ms=float(times[i].max() * 1e3),
                per_worker_ms=[float(t * 1e3) for t in times[i]],
                etr=bool(e_preds[i].etr_op != -1))
            ex = tracer.start("exchange", parent=ss, hop=i,
                              state=int(channels[i, 0]),
                              extremum=int(channels[i, 1]),
                              etr=int(channels[i, 2]))
            tracer.end(ex)
            tracer.end(ss)
        tracer.end(root, total=profile.total,
                   balance_eff=profile.balance_eff)
    return profile
