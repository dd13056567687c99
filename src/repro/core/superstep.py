"""Shared superstep core — the hop primitives every executor builds on.

The engine stack is three executors over ONE superstep vocabulary:

  engine.py             dense executor     — whole-graph tensor supersteps
  engine_sliced.py      sliced executor    — type-slice extents per hop (§Perf)
  engine_partitioned.py partitioned executor — per-worker shards + boundary
                                              exchange each hop (distributed)

This module owns the primitives they share, so a hop means the same thing in
all three:

  predicate evaluation   eval_predicate()        — type ∧ folded clauses over
                                                   property columns, returning
                                                   (match, validity) per mode
  edge masking           direction_mask(),
                         edge_predicate_weights() — edge predicate ∧ direction
  state algebra          init_state(), apply_validity(), apply_edge(),
                         state_total(), state_alive(), cells_to_buckets()
  ETR rank application   etr_weighted()          — rank tables + segment prefix
                                                   sums (exact, O(E) per hop)
                         etr_local_summaries()   — the same contraction from
                                                   SEGMENT-LOCAL prefix tables
                                                   (the partitioned executor's
                                                   rank-summary exchange)
  boundary exchange      p2p_exchange()          — ragged all-to-all over the
                                                   worker axis: only ghost
                                                   entries move (the
                                                   partitioned executor's
                                                   exchange, all channels)
  delivery               deliver()               — per-arrival-vertex sums
                                                   of per-edge counts: int32
                                                   prefix differences at the
                                                   arrival CSR offsets
                                                   (dense, sliced); segment-
                                                   sum scatter where edges
                                                   are unsorted or padded
                                                   (delta, partitioned)
                         fused_hop_deliver()     — the fused kernel hop
                                                   (gather → temporal mask →
                                                   segment-reduce in VMEM via
                                                   kernels.hop_scatter; the
                                                   impl='pallas' hot path of
                                                   every plain hop)
  extremum channel       minmax_seed(), minmax_edge(), deliver_extremum()
                         — the MIN/MAX aggregate's per-hop DP channel
                           (a segmented min/max scan read at segment ends
                           in the dense executor; segment_min/segment_max
                           where edges are unsorted or padded; the
                           partitioned executor exchanges it alongside the
                           count state)
  joins                  join_interval_counts(), join_interval_counts_edges()

Temporal modes (shared by all executors):

  MODE_STATIC    scalar counts per entity
  MODE_BUCKET    counts per time bucket          state [..., B]
  MODE_INTERVAL  counts per running-intersection interval cell
                 (start-bucket, end-bucket)      state [..., B, B+1]

State layout contract: every state/count tensor has the entity axis FIRST
(vertices, traversal edges, or padded per-worker slots) and the temporal-state
axes last.  All primitives here are elementwise over the entity axis except
``deliver``/``deliver_extremum`` (segment reductions) and the ETR prefix sums,
which is exactly what makes the partitioned executor possible: elementwise
steps shard trivially, the segment steps define the communication pattern
(and, because arrival segments never straddle workers, they all decompose
into per-worker segment ops + a boundary exchange).

Predicate evaluation (``vertex_predicate``), edge masking, delivery, the
extremum channel, the ETR machinery, delta delivery and the exchange each
run under a ``jax.named_scope`` from ``obs.trace.DEVICE_SCOPES``
(``vertex_pred``, ``edge_pred``, ``hop_deliver``, ``etr_prefix``,
``delta_deliver``, ``exchange``; the executors add ``src_gather`` and
``join``), so a device trace names the phase of each operation.  Where
scopes nest, the outermost names the phase.

Bucket edges are threaded through traces with the ``bucket_scope`` context
manager (a trace-scoped stack, not a function argument, so deeply nested
helpers stay signature-stable).
"""
from __future__ import annotations

import contextlib
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import intervals as iv
from . import query as Q
from ..kernels import hop_scatter as HK
from ..kernels.common import check_impl, resolve_interpret, use_pallas
from ..obs.trace import scope

MODE_STATIC = 0
MODE_BUCKET = 1
MODE_INTERVAL = 2

# ETR term kinds (rank-array rows in graph.EtrTables):
#   0: #(acc.start <  cur.start)     1: #(acc.start <= cur.start)
#   2: #(acc.start <  cur.end)       3: #(acc.end   <= cur.start)
# spec: (alpha, ((sign, term), ...)) st. result = alpha * n_acc + Σ sign * P[term]
ETR_SPECS = {
    (iv.FULLY_BEFORE, False): (0.0, ((1.0, 3),)),
    (iv.STARTS_BEFORE, False): (0.0, ((1.0, 0),)),
    (iv.FULLY_AFTER, False): (1.0, ((-1.0, 2),)),
    (iv.STARTS_AFTER, False): (1.0, ((-1.0, 1),)),
    (iv.OVERLAPS, False): (0.0, ((1.0, 2), (-1.0, 3))),
    (iv.FULLY_BEFORE, True): (1.0, ((-1.0, 2),)),
    (iv.STARTS_BEFORE, True): (1.0, ((-1.0, 1),)),
    (iv.FULLY_AFTER, True): (0.0, ((1.0, 3),)),
    (iv.STARTS_AFTER, True): (0.0, ((1.0, 0),)),
    (iv.OVERLAPS, True): (0.0, ((1.0, 2), (-1.0, 3))),
}

# Trace-scoped bucket-edge stack; executors push via bucket_scope().
TRACE_BEDGES: List = []


@contextlib.contextmanager
def bucket_scope(bedges):
    """Make ``bedges`` the current bucket edges for the enclosed trace."""
    TRACE_BEDGES.append(bedges)
    try:
        yield
    finally:
        TRACE_BEDGES.pop()


def current_bedges():
    return TRACE_BEDGES[-1] if TRACE_BEDGES else None


# =========================================================================
# clause evaluation
# =========================================================================
def _eval_prop_clause(col, value, cmp: int, mode: int, bedges, ent_life):
    """Evaluate one property clause over an entity set.

    Returns (match bool[N], validity) where validity is a bucket mask [N,B]
    (MODE_BUCKET), an interval int32[N,2] (MODE_INTERVAL), or None.
    """
    vals, life = col  # [N,S], [N,S,2]
    slot_eq = vals == value
    has_any = jnp.any(vals >= 0, axis=1)
    if cmp == Q.P_NEQ:
        match = has_any & ~jnp.any(slot_eq, axis=1)
        if mode == MODE_BUCKET:
            return match, iv.interval_to_bucket_mask(ent_life, bedges)
        if mode == MODE_INTERVAL:
            return match, ent_life
        return match, None
    # EQ / CONTAINS: any slot equal
    match = jnp.any(slot_eq, axis=1)
    if mode == MODE_BUCKET:
        slot_masks = iv.interval_to_bucket_mask(life, bedges)  # [N,S,B]
        valid = jnp.any(slot_masks & slot_eq[..., None], axis=1)
        return match, valid
    if mode == MODE_INTERVAL:
        idx = jnp.argmax(slot_eq, axis=1)
        sel = jnp.take_along_axis(life, idx[:, None, None], axis=1)[:, 0]  # [N,2]
        valid = jnp.where(match[:, None], sel, 0)
        return match, valid
    return match, None


def _eval_time_clause(ent_life, cmp_id: int, interval, mode: int, bedges):
    const_iv = jnp.broadcast_to(jnp.asarray(interval, jnp.int32), ent_life.shape)
    match = iv.compare(cmp_id, ent_life, const_iv)
    if mode == MODE_BUCKET:
        return match, iv.interval_to_bucket_mask(ent_life, bedges)
    if mode == MODE_INTERVAL:
        return match, ent_life
    return match, None


def _fold_clauses(parts, mode):
    """AND/OR left-fold of (conj, match, validity) triples."""
    acc_m, acc_v = None, None
    for conj, m, v in parts:
        if acc_m is None:
            acc_m, acc_v = m, v
            continue
        if conj == Q.AND:
            acc_m = acc_m & m
            if mode == MODE_BUCKET:
                acc_v = acc_v & v
            elif mode == MODE_INTERVAL:
                acc_v = iv.intersect(acc_v, v)
        else:  # OR
            new_m = acc_m | m
            if mode == MODE_BUCKET:
                acc_v = (acc_v & acc_m[:, None]) | (v & m[:, None])
            elif mode == MODE_INTERVAL:
                # span approximation for OR in interval mode (documented)
                acc_v = jnp.where(
                    (acc_m & ~m)[:, None], acc_v,
                    jnp.where((m & ~acc_m)[:, None], v, iv.span(acc_v, v)),
                )
            acc_m = new_m
    return acc_m, acc_v


def eval_predicate(
    props: Dict[int, tuple],
    ent_type,
    ent_life,
    req_type: int,
    clauses: Sequence[Q.Clause],
    params,
    pbase: int,
    mode: int,
    bedges,
):
    """Full predicate = type check ∧ folded clauses; returns (match, validity).

    ``params`` carries the data values: row i = (value, t_lo, t_hi) for the
    i-th clause of the whole query; ``pbase`` is this predicate's first row.
    """
    n = ent_life.shape[0]
    match = jnp.ones((n,), bool)
    if req_type >= 0:
        match = ent_type == req_type
    match = match & (ent_life[:, 0] < ent_life[:, 1])
    if mode == MODE_BUCKET:
        validity = iv.interval_to_bucket_mask(ent_life, bedges)
    elif mode == MODE_INTERVAL:
        validity = ent_life
    else:
        validity = None
    parts = []
    for i, c in enumerate(clauses):
        row = params[pbase + i]
        if c.kind == Q.K_PROP:
            col = props[c.key]
            m, v = _eval_prop_clause(col, row[0], c.cmp, mode, bedges, ent_life)
        else:
            m, v = _eval_time_clause(ent_life, c.cmp, row[1:3], mode, bedges)
        parts.append((c.conj, m, v))
    if parts:
        cm, cv = _fold_clauses(parts, mode)
        match = match & cm
        if mode == MODE_BUCKET:
            validity = validity & cv
        elif mode == MODE_INTERVAL:
            validity = iv.intersect(validity, cv)
    return match, validity


@scope("vertex_pred")
def vertex_predicate(gdev, vp: Q.VertexPredicate, params, pbase, mode,
                     bedges):
    """(match, validity) of one vertex predicate over every vertex."""
    return eval_predicate(gdev["vprops"], gdev["v_type"], gdev["v_life"],
                          vp.vtype, vp.clauses, params, pbase, mode, bedges)


# =========================================================================
# edge masking
# =========================================================================
def direction_mask(t_isfwd, direction: int):
    """bool mask selecting traversal edges compatible with a hop direction."""
    if direction == Q.DIR_OUT:
        return t_isfwd == 1
    if direction == Q.DIR_IN:
        return t_isfwd == 0
    return jnp.ones_like(t_isfwd, bool)


@scope("edge_pred")
def edge_predicate_weights(gdev, ep: Q.EdgePredicate, params, pbase, mode, bedges):
    """(weight mask bool[2E], bucket/interval validity) for one hop."""
    t_life = gdev["t_life"]
    match, validity = eval_predicate(
        gdev["eprops_t"], gdev["t_type"], t_life, ep.etype, ep.clauses,
        params, pbase, mode, bedges,
    )
    return (match & direction_mask(gdev["t_isfwd"], ep.direction)), validity


# =========================================================================
# mode-generic state ops
# =========================================================================
def init_state(match, validity, mode: int, n_buckets: int):
    """Seed DP state from a vertex predicate result."""
    if mode == MODE_STATIC:
        return match.astype(jnp.float32)
    if mode == MODE_BUCKET:
        return (match[:, None] & validity).astype(jnp.float32)
    # INTERVAL: one-hot cell at (start_bucket, end_bucket); cells [B, B+1]
    B = n_buckets
    sb, eb = _interval_to_cells(validity, B)
    cell = (
        jax.nn.one_hot(sb, B, dtype=jnp.float32)[:, :, None]
        * jax.nn.one_hot(eb, B + 1, dtype=jnp.float32)[:, None, :]
    )
    return cell * match[:, None, None].astype(jnp.float32)


def _interval_to_cells(ivl, B):
    """Map int32[N,2] intervals to (start_bucket, end_bucket) cell ids using
    the bucket edges of the enclosing bucket_scope()."""
    bedges = TRACE_BEDGES[-1]
    sb = jnp.clip(jnp.searchsorted(bedges, ivl[:, 0], side="right") - 1, 0, B - 1)
    eb = jnp.clip(jnp.searchsorted(bedges, ivl[:, 1], side="left"), 0, B)
    empty = ivl[:, 0] >= ivl[:, 1]
    eb = jnp.where(empty, sb, eb)  # empty → zero-width cell (filtered later)
    return sb, eb


def apply_validity(state, match, validity, mode: int):
    """Multiply state by a predicate's (match, validity) at its entity."""
    if mode == MODE_STATIC:
        return state * match.astype(jnp.float32)
    if mode == MODE_BUCKET:
        return state * (match[:, None] & validity).astype(jnp.float32)
    # INTERVAL: clamp running-intersection cells by the validity interval
    B = state.shape[-2]
    sb, eb = _interval_to_cells(validity, B)
    out = _clamp_start(state, sb)
    out = _clamp_end(out, eb)
    out = out * match[..., None, None].astype(jnp.float32)
    return _mask_valid_cells(out)


def apply_edge(src_val, wmask, evalidity, mode: int):
    """Apply a hop's edge weights to gathered source values (per-edge)."""
    if mode == MODE_STATIC:
        return src_val * wmask.astype(jnp.float32)
    if mode == MODE_BUCKET:
        return src_val * (wmask[:, None] & evalidity).astype(jnp.float32)
    return apply_validity(src_val, wmask, evalidity, mode)


def _clamp_start(state, ps):
    """cells[n, s, e] move to (max(s, ps[n]), e)."""
    B = state.shape[-2]
    cum = jnp.cumsum(state, axis=-2)
    keep = (jnp.arange(B)[None, :] > ps[:, None]).astype(state.dtype)
    cum_at = jnp.take_along_axis(cum, ps[:, None, None], axis=-2)[:, 0, :]
    onehot = jax.nn.one_hot(ps, B, dtype=state.dtype)
    return state * keep[:, :, None] + onehot[:, :, None] * cum_at[:, None, :]


def _clamp_end(state, pe):
    """cells[n, s, e] move to (s, min(e, pe[n]))."""
    Bp1 = state.shape[-1]
    rcum = jnp.cumsum(state[..., ::-1], axis=-1)[..., ::-1]
    keep = (jnp.arange(Bp1)[None, :] < pe[:, None]).astype(state.dtype)
    cum_at = jnp.take_along_axis(rcum, pe[:, None, None], axis=-1)[:, :, 0]
    onehot = jax.nn.one_hot(pe, Bp1, dtype=state.dtype)
    return state * keep[:, None, :] + onehot[:, None, :] * cum_at[:, :, None]


def _mask_valid_cells(state):
    B, Bp1 = state.shape[-2], state.shape[-1]
    s_ids = jnp.arange(B)[:, None]
    e_ids = jnp.arange(Bp1)[None, :]
    return state * (s_ids < e_ids).astype(state.dtype)


def state_total(state, mode):
    if mode == MODE_STATIC:
        return jnp.sum(state)
    if mode == MODE_BUCKET:
        return jnp.sum(state, axis=0)  # per-bucket totals
    return jnp.sum(_mask_valid_cells(state))


def state_alive(state, mode):
    """bool[N]: entities whose count state is non-zero anywhere (static
    scalar, any bucket, or any interval cell) — the liveness gate of the
    extremum channel."""
    if mode == MODE_STATIC:
        return state > 0
    return state.sum(axis=tuple(range(1, state.ndim))) > 0


def cells_to_buckets(state):
    """[N,B,B+1] running-interval cells → [N,B] per-bucket time series."""
    B = state.shape[-2]
    out = []
    s_ids = jnp.arange(B)[:, None]
    e_ids = jnp.arange(B + 1)[None, :]
    for b in range(B):
        m = ((s_ids <= b) & (e_ids > b)).astype(state.dtype)
        out.append(jnp.sum(state * m, axis=(-2, -1)))
    return jnp.stack(out, axis=-1)


# =========================================================================
# point-to-point boundary exchange (the distributed executor's collective)
# =========================================================================
@scope("exchange")
def p2p_exchange(rows_w, local_src, send_slot, recv_slot, n_slots: int,
                 axis_name: Optional[str] = None, fill=0.0):
    """Ragged all-to-all over the worker axis — the boundary exchange.

    Every receive-buffer entry (a halo vertex's state, or an owned edge's
    ETR rank summary) lives with exactly ONE owner.  The partitioner's
    routing tables split them into a local copy (entries the receiver owns
    itself) and one ragged lane per worker pair carrying just the ghost
    entries — so only ghost entries move, with no global [V]/[2E] buffer and
    no psum reduction (ownership is exclusive: the exchange is a copy).

      rows_w     [Wl, K, *TS]   owner-local source rows (this device's
                                workers; Wl = W when simulated)
      local_src  int32[Wl, N]   own-row slot per self-owned receive entry,
                                pad = K (reads the ``fill`` row)
      send_slot  int32[Wl, W, C] own-row slot of the k-th row local worker i
                                sends to GLOBAL worker d, pad = K
      recv_slot  int32[Wl, W, C] receive-buffer position where the k-th row
                                from GLOBAL worker s lands, pad = N (a trash
                                slot, sliced off)
      n_slots    N              receive-buffer extent

    With ``axis_name`` unset the worker axis is fully local (the vmap
    simulation) and the all-to-all is an axis transpose; under shard_map the
    same payload moves with one ``lax.all_to_all`` over the mesh axis.  Both
    are pure data movement over identical tables, which is what makes the
    sharded path bit-identical to the simulation.  Lanes are padded to C
    (the max per-pair ghost count); the ragged content — Σ ghost entries —
    is the real traffic reported by ``PartitionArrays.exchange_volume()`` /
    ``etr_exchange_volume()``.
    """
    Wl, K = rows_w.shape[:2]
    W, C = send_slot.shape[1:3]
    ts = rows_w.shape[2:]
    pad = jnp.full((Wl, 1) + ts, fill, rows_w.dtype)
    rows_pad = jnp.concatenate([rows_w, pad], axis=1)
    take = jax.vmap(lambda r, s: r[s])
    local = take(rows_pad, local_src)                    # [Wl, N, *TS]
    payload = take(rows_pad, send_slot)                  # [Wl, W, C, *TS]
    if axis_name is None:
        received = jnp.swapaxes(payload, 0, 1)           # [W_dst, W_src, C]
    else:
        D = W // Wl
        q = payload.reshape((Wl, D, Wl, C) + ts)         # split dst by device
        q = jnp.moveaxis(q, 1, 0)                        # [D, Wl_src, Wl_dst, C]
        a = jax.lax.all_to_all(q, axis_name, 0, 0)       # [D_src, Wl_src, Wl_dst, C]
        received = jnp.moveaxis(a, 2, 0).reshape((Wl, W, C) + ts)

    def place(loc, rec, pos):
        buf = jnp.concatenate(
            [loc, jnp.full((1,) + ts, fill, rows_w.dtype)], axis=0)
        return buf.at[pos.reshape(-1)].set(rec.reshape((-1,) + ts))[:n_slots]

    return jax.vmap(place)(local, received, recv_slot)


# =========================================================================
# delivery
# =========================================================================
@scope("hop_deliver")
def deliver(cnt_e, seg_ids, num_segments: int, indices_are_sorted: bool = True,
            impl: str = "xla", layout=None, ptr=None):
    """Per-arrival-vertex sum of per-edge counts — the message delivery of
    one superstep.

    ``impl`` selects the lowering.  ``'pallas'``/``'pallas_interpret'`` with
    a ``kernels.hop_scatter`` ``HopLayout`` over the same (static, sorted)
    seg_ids runs the blocked scatter-as-matmul kernel.  Otherwise, given
    ``ptr`` (int32 ``[num_segments + 1]``, the CSR offsets of arrival-sorted
    edges), the sums are prefix differences with no scatter: an int32
    running sum along the edge axis read at the segment bounds.  Without
    ``ptr`` (unsorted delta edges, padded per-worker slots) it is the XLA
    segment-sum scatter.  All three agree bit for bit while counts are
    exact integers in float32, the engine's invariant: int32 addition wraps
    exactly, so each segment's difference is exact whenever its sum fits
    int32, which covers every sum float32 holds exactly."""
    if use_pallas(check_impl(impl)) and layout is not None:
        return HK.scatter_deliver(cnt_e, layout.tables, num_segments,
                                  layout.block_v, impl=impl)
    if ptr is not None:
        return _prefix_deliver(cnt_e, ptr)
    return jax.ops.segment_sum(
        cnt_e, seg_ids, num_segments=num_segments,
        indices_are_sorted=indices_are_sorted,
    )


def _prefix_deliver(cnt_e, ptr):
    """Segment sums over CSR offsets ``ptr`` as differences of an int32
    running sum: ``S[ptr[v+1]] - S[ptr[v]]`` with ``S[0] = 0``."""
    ts = cnt_e.shape[1:]
    if cnt_e.shape[0] == 0:
        return jnp.zeros((ptr.shape[0] - 1,) + ts, cnt_e.dtype)
    run = jax.lax.cumsum(cnt_e.astype(jnp.int32), axis=0)
    at = run[jnp.maximum(ptr - 1, 0)]            # S[ptr], less the zero row
    at = jnp.where((ptr > 0).reshape((-1,) + (1,) * len(ts)), at, 0)
    return (at[1:] - at[:-1]).astype(cnt_e.dtype)


@scope("hop_deliver")
def fused_hop_deliver(
    state,                       # [N, *TS] source-state table
    src_slot,                    # int32[E] — source row per edge; N = zero row
    wmask,                       # bool[E] edge-predicate ∧ direction match
    evalid,                      # temporal validity: None / bool[E, B] /
                                 # int32[E, 2] interval (per mode)
    mode: int,
    lt: Dict,                    # HopLayout.tables (or a worker-sliced row of
                                 #   stacked tables — a uniform array pytree,
                                 #   so executors can vmap it with in_axes=0)
    block_v: int,
    num_segments: int,
    impl: str = "pallas",
    mch=None,                    # optional extremum channel table [N]
    minmax_op: int = Q.AGG_MIN,
) -> Tuple[jnp.ndarray, Optional[jnp.ndarray]]:
    """One fused traversal hop: gather → temporal mask → segment-reduce.

    Pallas-only twin of the three-step XLA hop (``state[src]`` gather,
    ``apply_edge``, ``deliver``) that never materialises the per-edge
    ``[E, *TS]`` state: the ``kernels.hop_scatter`` kernel gathers, weights
    and prefix-reduces per destination block in VMEM.  When ``mch`` is
    given, the MIN/MAX extremum channel is gathered, liveness-gated by the
    in-VMEM contributions, and min/max-reduced alongside (the
    ``minmax_edge`` + ``deliver_extremum`` pair of the XLA path).

    ``evalid``/``mch`` may be 0-d placeholders for "absent" (the profiling
    and vmap call sites can't pass None through mapped axes).

    Returns (arrivals [num_segments, *TS], mch_out [num_segments] | None).
    """
    assert use_pallas(check_impl(impl)), "fused_hop_deliver is the kernel path"
    interpret = resolve_interpret(None, impl)
    if evalid is not None and getattr(evalid, "ndim", 1) == 0:
        evalid = None
    if mch is not None and getattr(mch, "ndim", 1) == 0:
        mch = None
    N = state.shape[0]
    ts = state.shape[1:]
    gather_idx, valid = lt["gather"], lt["valid"]
    n_blocks, block_e = lt["ldst"].shape
    src_sl = HK.slots(src_slot.astype(jnp.int32), gather_idx, valid,
                      N).reshape(n_blocks, block_e)
    mch_p = None
    neutral = 0.0
    op_is_min = minmax_op == Q.AGG_MIN
    if mch is not None:
        neutral = float(np.inf if op_is_min else -np.inf)
        mch_p = jnp.concatenate(
            [mch.astype(jnp.float32), jnp.full((1,), neutral, jnp.float32)]
        )[:, None]
    if mode == MODE_INTERVAL:
        B = state.shape[-2]
        state_p = jnp.concatenate(
            [state.reshape(N, B * (B + 1)),
             jnp.zeros((1, B * (B + 1)), state.dtype)], axis=0)
        w = HK.slots(wmask.astype(jnp.float32), gather_idx, valid,
                     0.0).reshape(n_blocks, block_e)
        sb, eb = _interval_to_cells(evalid, B)
        sb_sl = HK.slots(sb.astype(jnp.int32), gather_idx, valid,
                         0).reshape(n_blocks, block_e)
        eb_sl = HK.slots(eb.astype(jnp.int32), gather_idx, valid,
                         0).reshape(n_blocks, block_e)
        out, mch_out = HK.fused_hop_interval_pallas(
            state_p, src_sl, w, sb_sl, eb_sl, lt["sstart"], lt["send"],
            lt["ldst"], block_v, B, interpret=interpret, mch_p=mch_p,
            neutral=neutral, op_is_min=op_is_min)
        arrivals = out[:num_segments].reshape(num_segments, B, B + 1)
    else:
        C = 1 if mode == MODE_STATIC else state.shape[1]
        state_p = jnp.concatenate(
            [state.reshape(N, C), jnp.zeros((1, C), state.dtype)], axis=0)
        if mode == MODE_STATIC:
            wv = wmask.astype(jnp.float32)[:, None]
        else:
            wv = (wmask[:, None] & evalid).astype(jnp.float32)
        w_cols = HK.slots(wv, gather_idx, valid, 0.0).reshape(
            n_blocks, block_e, C)
        out, mch_out = HK.fused_hop_cols_pallas(
            state_p, src_sl, w_cols, lt["sstart"], lt["send"], lt["ldst"],
            block_v, interpret=interpret, mch_p=mch_p, neutral=neutral,
            op_is_min=op_is_min)
        arrivals = out[:num_segments].reshape((num_segments,) + ts)
    if mch_out is not None:
        mch_out = mch_out[:num_segments]
    return arrivals, mch_out


# =========================================================================
# extremum (MIN/MAX aggregate) channel
# =========================================================================
def minmax_neutral(op: int):
    """The aggregation-neutral element of the extremum channel."""
    return jnp.float32(np.inf if op == Q.AGG_MIN else -np.inf)


def minmax_seed(state, col_vals, op: int, mode: int):
    """Seed the per-entity extremum channel from the aggregate's property
    column: the first-slot value where the count state is alive, neutral
    elsewhere."""
    base = col_vals[:, 0].astype(jnp.float32)
    return jnp.where(state_alive(state, mode), base, minmax_neutral(op))


@scope("hop_deliver")
def minmax_edge(mch_src, cnt_e, op: int, mode: int):
    """Per-edge extremum message: the source channel where the edge carries
    any live count, neutral elsewhere (so dead/pad edges cannot win)."""
    return jnp.where(state_alive(cnt_e, mode), mch_src, minmax_neutral(op))


@scope("hop_deliver")
def deliver_extremum(m_e, seg_ids, num_segments: int, op: int,
                     indices_are_sorted: bool = True, impl: str = "xla",
                     layout=None, ptr=None):
    """Extremum twin of ``deliver``: per-arrival-vertex min/max of the
    per-edge channel, the neutral ±inf on empty segments.  Min/max is
    order-independent, so every lowering, and per-worker deliveries over
    owned segments, match exactly.  The lowerings mirror ``deliver``'s: the
    blocked masked-extremum kernel with a layout; given ``ptr``, a
    segmented scan over the arrival-sorted edges read at each segment's
    last edge; else the XLA segment_min/segment_max scatter."""
    if use_pallas(check_impl(impl)) and layout is not None:
        # m_e is already liveness-gated by minmax_edge: every slot is "alive"
        return HK.scatter_extremum(
            m_e, jnp.ones_like(m_e), layout.tables, num_segments,
            layout.block_v, neutral=float(minmax_neutral(op)),
            op_is_min=(op == Q.AGG_MIN), impl=impl)
    if ptr is not None:
        return _scan_extremum(m_e, seg_ids, ptr, op)
    seg = jax.ops.segment_min if op == Q.AGG_MIN else jax.ops.segment_max
    return seg(m_e, seg_ids, num_segments=num_segments,
               indices_are_sorted=indices_are_sorted)


def _scan_extremum(m_e, seg_ids, ptr, op: int):
    """Segmented inclusive min/max scan of ``m_e [E]`` over sorted
    ``seg_ids``, read at the segment ends ``ptr[1:] - 1``.

    The scan doubles its reach each step (step d folds in the value d edges
    back while that edge lies in the same segment), so it takes
    ceil(log2(longest segment)) steps of elementwise work."""
    neutral = minmax_neutral(op)
    if m_e.shape[0] == 0:
        return jnp.full((ptr.shape[0] - 1,), neutral, m_e.dtype)
    comb = jnp.minimum if op == Q.AGG_MIN else jnp.maximum
    idx = jnp.arange(m_e.shape[0], dtype=jnp.int32)
    start = jnp.concatenate([jnp.ones((1,), bool), seg_ids[1:] != seg_ids[:-1]])
    first = jax.lax.cummax(jnp.where(start, idx, 0))   # own segment's 1st edge
    longest = jnp.max(ptr[1:] - ptr[:-1])

    def step(carry):
        d, v = carry
        return 2 * d, jnp.where(idx - d >= first,
                                comb(v, jnp.roll(v, d, axis=0)), v)

    _, scan = jax.lax.while_loop(lambda c: c[0] < longest, step,
                                 (jnp.int32(1), m_e))
    end = scan[jnp.maximum(ptr[1:] - 1, 0)]
    return jnp.where(ptr[1:] > ptr[:-1], end, neutral)


# =========================================================================
# delta-segment delivery (base-CSR + delta execution, graphdata/ingest.py)
# =========================================================================
@scope("delta_deliver")
def delta_hop_deliver(delta, ep, sv, params, pbase, mode: int, V: int,
                      mch=None, minmax_op=Q.AGG_MIN):
    """One hop's arrival contribution from a padded delta-edge segment.

    ``delta`` is a ``DeltaSpec.device()`` dict shaped like a tiny unsorted
    gdev (t_src/t_dst/t_life/t_type/t_isfwd/eprops_t over 2·capacity slots
    plus a ``valid`` mask killing the padding).  The hop's edge predicate is
    evaluated over the delta slots exactly as over base traversal edges, the
    per-edge counts are delivered with an UNSORTED segment-sum (delta edges
    are in appended order, not arrival order), and the extremum channel
    rides along when ``mch`` is given.  Because counts are exact small
    integers in float32, base-sum + delta-sum equals the merged graph's
    single sorted sum bit-for-bit — the invariant that makes the base+delta
    executable interchangeable with a from-scratch epoch build.

    Returns (arrival counts [V, *TS], extremum [V] | None) to be combined
    into the base hop's delivery (add / min-max respectively).
    """
    bedges = current_bedges()
    wmask, evalid = edge_predicate_weights(delta, ep, params, pbase, mode,
                                           bedges)
    wmask = wmask & delta["valid"]
    cnt = apply_edge(sv[delta["t_src"]], wmask, evalid, mode)
    add = deliver(cnt, delta["t_dst"], V, indices_are_sorted=False)
    mm = None
    if mch is not None:
        m_e = minmax_edge(mch[delta["t_src"]], cnt, minmax_op, mode)
        mm = deliver_extremum(m_e, delta["t_dst"], V, minmax_op,
                              indices_are_sorted=False)
    return add, mm


# =========================================================================
# ETR prefix machinery
# =========================================================================
@scope("etr_prefix")
def etr_weighted(gdev, cnt_e_prev, op: int, backward: bool, use_arr: bool):
    """Per current traversal edge: Σ over accumulated arrivals at its vertex
    of cnt × [ETR condition], via rank tables (exact)."""
    alpha, terms = ETR_SPECS[(op, backward)]
    perm_s = gdev["etr_perm_start"]
    perm_e = gdev["etr_perm_end"]
    ranks = gdev["etr_arr_ranks"] if use_arr else gdev["etr_dep_ranks"]
    ptr = gdev["arr_ptr"]
    segv = gdev["t_dst"] if use_arr else gdev["t_src"]

    trailing = cnt_e_prev.shape[1:]
    zero = jnp.zeros((1,) + trailing, cnt_e_prev.dtype)

    S_s = jnp.concatenate([zero, jnp.cumsum(cnt_e_prev[perm_s], axis=0)], axis=0)
    need_end = etr_needs_end(op, backward)
    S_e = (
        jnp.concatenate([zero, jnp.cumsum(cnt_e_prev[perm_e], axis=0)], axis=0)
        if need_end
        else None
    )
    base_pos = ptr[segv]
    base_s = S_s[base_pos]
    out = 0.0
    if alpha:
        n_acc = S_s[ptr[segv + 1]] - base_s
        out = alpha * n_acc
    for sign, term in terms:
        S = S_e if term == 3 else S_s
        base = (S_e[base_pos] if term == 3 else base_s)
        val = S[base_pos + ranks[term]] - base
        out = out + sign * val
    return out


def etr_needs_end(op: int, backward: bool) -> bool:
    """Does this ETR spec read the (dst, life-end)-ordered prefix table?"""
    _, terms = ETR_SPECS[(op, backward)]
    return any(t == 3 for _, t in terms)


@scope("etr_prefix")
def etr_local_summaries(cnt_perm_s, cnt_perm_e, base, seg_len, ranks,
                        op: int, backward: bool):
    """Per-edge ETR rank summaries from SEGMENT-LOCAL prefix tables.

    The contraction of ``etr_weighted`` only ever takes prefix DIFFERENCES
    inside one arrival segment, so a worker owning whole segments can compute
    it from prefix sums over just its own prev-hop counts — this function is
    that local step, and its outputs are exactly the per-edge values the
    partitioned executor exchanges (boundary rank summaries) on ETR hops.

      cnt_perm_s  [K, *TS] — owned prev-hop counts in (dst, life-start) order
      cnt_perm_e  [K, *TS] — same in (dst, life-end) order; may be None when
                             ``not etr_needs_end(op, backward)``
      base        int32[S] — local prefix index of each produced edge's
                             source-segment base (0 ≤ base ≤ K)
      seg_len     int32[S] — that segment's length (base + seg_len ≤ K)
      ranks       int32[4, S] — the global rank tables gathered at the
                             produced edges (within-segment offsets)

    Returns [S, *TS] summaries; pad rows (base = len = ranks = 0) return 0.
    Matches ``etr_weighted`` exactly whenever the count sums are exactly
    representable (all engine counts are small integers in float32).
    """
    alpha, terms = ETR_SPECS[(op, backward)]
    trailing = cnt_perm_s.shape[1:]
    zero = jnp.zeros((1,) + trailing, cnt_perm_s.dtype)
    S_s = jnp.concatenate([zero, jnp.cumsum(cnt_perm_s, axis=0)], axis=0)
    S_e = (
        jnp.concatenate([zero, jnp.cumsum(cnt_perm_e, axis=0)], axis=0)
        if cnt_perm_e is not None
        else None
    )
    base_s = S_s[base]
    out = 0.0
    if alpha:
        out = alpha * (S_s[base + seg_len] - base_s)
    for sign, term in terms:
        S = S_e if term == 3 else S_s
        b0 = S_e[base] if term == 3 else base_s
        out = out + sign * (S[base + ranks[term]] - b0)
    return out


# =========================================================================
# joins
# =========================================================================
def join_interval_counts(L, R):
    """Distinct-path count from left/right running-intersection cell states.

    D = Σ_v Σ_{cells} L·R·[intervals overlap]; computed via the complement
    (total − disjoint) with cumsum contractions — O(V·B²).
    L, R: [V, B, B+1].
    """
    totL = L.sum(axis=(1, 2))
    totR = R.sum(axis=(1, 2))
    Le = L.sum(axis=1)      # [V, B+1] marginal over start
    Ls = L.sum(axis=2)      # [V, B]   marginal over end
    Re = R.sum(axis=1)
    Rs = R.sum(axis=2)
    # pairs with L.end <= R.start  (cells: e1 <= s2)
    cumLe = jnp.cumsum(Le, axis=1)  # Σ_{e1 <= x}
    d1 = jnp.einsum("vb,vb->v", Rs, cumLe[:, : Rs.shape[1]])
    # pairs with R.end <= L.start
    cumRe = jnp.cumsum(Re, axis=1)
    d2 = jnp.einsum("vb,vb->v", Ls, cumRe[:, : Ls.shape[1]])
    return totL * totR - d1 - d2


# identical contraction at traversal-edge granularity (ETR-at-join)
join_interval_counts_edges = join_interval_counts
