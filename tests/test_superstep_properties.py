"""Property tests for the superstep interval/bucket state algebra.

The running-intersection cell algebra (``apply_validity`` clamping in
MODE_INTERVAL) must behave like interval intersection at bucket granularity:
idempotent, commutative, and — whenever the exact intersection is non-empty —
equal to clamping by ``iv.intersect`` directly.  (When the exact intersection
is empty the sequential clamps may legitimately keep a bucket straddling the
gap: the algebra is bucket-granular by design; see the conformance-harness
docstring.)  Delivery reductions are checked against plain numpy oracles.

Intervals are drawn INSIDE the bucketed span, mirroring the engine invariant
that every entity lifespan lies within the graph lifespan the bucket edges
cover (out-of-span intervals would be clipped into the edge buckets).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

hypothesis = pytest.importorskip(
    "hypothesis", reason="property tests need the optional hypothesis dep "
    "(pip install -r requirements-dev.txt)")
from hypothesis import given, settings  # noqa: E402
from hypothesis import strategies as st  # noqa: E402

from repro.core import intervals as iv  # noqa: E402
from repro.core import query as Q  # noqa: E402
from repro.core import superstep as SS  # noqa: E402

B = 5
SPAN = 100
BEDGES = jnp.asarray(iv.bucket_edges(0, SPAN, B))
N = 6

ivl = st.tuples(st.integers(0, SPAN - 1), st.integers(1, SPAN)).map(
    lambda t: (t[0], min(t[0] + t[1], SPAN)))
ivls = st.lists(ivl, min_size=N, max_size=N).map(
    lambda xs: jnp.asarray(np.asarray(xs, np.int32)))
matches = st.lists(st.booleans(), min_size=N, max_size=N).map(
    lambda xs: jnp.asarray(np.asarray(xs)))
cells = st.lists(
    st.lists(st.integers(0, 3), min_size=B * (B + 1), max_size=B * (B + 1)),
    min_size=N, max_size=N,
).map(lambda xs: jnp.asarray(
    np.asarray(xs, np.float32).reshape(N, B, B + 1)))


def _apply(state, m, v):
    with SS.bucket_scope(BEDGES):
        return np.asarray(SS.apply_validity(state, m, v, SS.MODE_INTERVAL))


@settings(max_examples=50, deadline=None)
@given(cells, matches, ivls)
def test_clamp_idempotent(state, m, v):
    once = _apply(state, m, v)
    assert np.array_equal(_apply(jnp.asarray(once), m, v), once)


@settings(max_examples=50, deadline=None)
@given(cells, matches, ivls, ivls)
def test_clamp_commutes(state, m, v1, v2):
    ab = _apply(jnp.asarray(_apply(state, m, v1)), m, v2)
    ba = _apply(jnp.asarray(_apply(state, m, v2)), m, v1)
    assert np.array_equal(ab, ba)


@settings(max_examples=50, deadline=None)
@given(cells, matches, ivls, ivls)
def test_clamp_join_matches_exact_intersection(state, m, v1, v2):
    """Sequential clamping ≡ clamping by the exact intersection, wherever
    that intersection is non-empty (the associativity of the join)."""
    ab = _apply(jnp.asarray(_apply(state, m, v1)), m, v2)
    inter = iv.intersect(v1, v2)
    direct = _apply(state, m, inter)
    nonempty = np.asarray(inter[:, 0] < inter[:, 1])
    assert np.array_equal(ab[nonempty], direct[nonempty])


@settings(max_examples=50, deadline=None)
@given(cells)
def test_valid_cell_mask_idempotent(state):
    once = SS._mask_valid_cells(state)
    assert np.array_equal(np.asarray(SS._mask_valid_cells(once)),
                          np.asarray(once))


@settings(max_examples=50, deadline=None)
@given(matches, ivls)
def test_interval_init_projects_to_bucket_init(m, v):
    """cells_to_buckets ∘ interval-init ≡ bucket-init: the two temporal modes
    agree on the per-bucket view of a freshly seeded state."""
    with SS.bucket_scope(BEDGES):
        ic = SS.init_state(m, v, SS.MODE_INTERVAL, B)
        bmask = iv.interval_to_bucket_mask(v, BEDGES)
        binit = SS.init_state(m, bmask, SS.MODE_BUCKET, B)
        assert np.array_equal(np.asarray(SS.cells_to_buckets(ic)),
                              np.asarray(binit))


segments = st.integers(2, 6).flatmap(lambda ns: st.tuples(
    st.just(ns),
    st.lists(st.integers(0, ns - 1), min_size=1, max_size=24),
    ))


@settings(max_examples=50, deadline=None)
@given(segments, st.data())
def test_deliver_extremum_matches_numpy(seg_spec, data):
    """Per-segment segment_min/segment_max against a numpy loop oracle,
    including empty segments (→ the aggregation-neutral ±inf)."""
    nseg, seg_list = seg_spec
    seg = np.sort(np.asarray(seg_list, np.int32))
    vals = np.asarray(
        data.draw(st.lists(st.integers(-50, 50), min_size=len(seg),
                           max_size=len(seg))), np.float32)
    for op in (Q.AGG_MIN, Q.AGG_MAX):
        got = np.asarray(SS.deliver_extremum(
            jnp.asarray(vals), jnp.asarray(seg), nseg, op))
        want = np.full(nseg, np.asarray(SS.minmax_neutral(op)), np.float32)
        for s, v in zip(seg, vals):
            want[s] = min(want[s], v) if op == Q.AGG_MIN else max(want[s], v)
        assert np.array_equal(got, want), op


@settings(max_examples=50, deadline=None)
@given(segments, st.data())
def test_deliver_matches_numpy(seg_spec, data):
    nseg, seg_list = seg_spec
    seg = np.sort(np.asarray(seg_list, np.int32))
    vals = np.asarray(
        data.draw(st.lists(st.integers(-50, 50), min_size=len(seg),
                           max_size=len(seg))), np.float32)
    got = np.asarray(SS.deliver(jnp.asarray(vals), jnp.asarray(seg), nseg))
    want = np.zeros(nseg, np.float32)
    np.add.at(want, seg, vals)
    assert np.array_equal(got, want)


# -------------------------------------------------------------------------
# the sorted-CSR lowerings (``ptr=``) against numpy and the scatter
# -------------------------------------------------------------------------
def _csr(lengths):
    """(sorted seg ids int32[E], CSR offsets int32[len + 1])."""
    lengths = np.asarray(lengths, np.int64)
    ptr = np.zeros(len(lengths) + 1, np.int32)
    np.cumsum(lengths, out=ptr[1:])
    return np.repeat(np.arange(len(lengths), dtype=np.int32), lengths), ptr


#: segment lengths per case: empty segments (first, inner and last), no
#: edges at all, one-edge segments, and hubs just below, at and above a
#: power of two
SEGMENT_CASES = {
    "empty": [0, 3, 0, 0, 2, 0],
    "no_edges": [0, 0, 0],
    "single_edge": [1, 1, 0, 1],
    "hub": [2, 0, 300, 1, 0, 256, 257],
}
#: trailing state shapes: static [E], bucket [E, B], interval [E, B, B+1]
TRAILING = {"static": (), "bucket": (B,), "interval": (B, B + 1)}
BATCH = 3


def _numpy_sum(vals, seg, nseg):
    want = np.zeros((nseg,) + vals.shape[1:], np.float32)
    np.add.at(want, seg, vals)
    return want


def _check_sum_lowerings(vals, seg, ptr):
    """ptr lowering == numpy == segment_sum scatter, bit for bit, alone and
    under a batch vmap (``vals`` carries the batch axis first)."""
    nseg = len(ptr) - 1
    seg_j, ptr_j = jnp.asarray(seg), jnp.asarray(ptr)
    by_ptr = jax.vmap(lambda c: SS.deliver(c, seg_j, nseg, ptr=ptr_j))
    by_scatter = jax.vmap(lambda c: SS.deliver(c, seg_j, nseg))
    want = np.stack([_numpy_sum(v, seg, nseg) for v in vals])
    assert np.array_equal(np.asarray(by_ptr(jnp.asarray(vals))), want)
    assert np.array_equal(np.asarray(by_scatter(jnp.asarray(vals))), want)
    one = SS.deliver(jnp.asarray(vals[0]), seg_j, nseg, ptr=ptr_j)
    assert np.array_equal(np.asarray(one), want[0])


@pytest.mark.parametrize("trailing", sorted(TRAILING))
@pytest.mark.parametrize("case", sorted(SEGMENT_CASES))
def test_deliver_ptr_matches_numpy_and_scatter(case, trailing):
    seg, ptr = _csr(SEGMENT_CASES[case])
    rng = np.random.default_rng(len(seg))
    shape = (BATCH, len(seg)) + TRAILING[trailing]
    vals = (rng.integers(0, 50, shape) * (rng.random(shape) < 0.7)).astype(
        np.float32)
    _check_sum_lowerings(vals, seg, ptr)


@pytest.mark.parametrize("trailing", sorted(TRAILING))
def test_deliver_ptr_exact_past_float32_running_total(trailing):
    """Each segment sums to 2^24 - 4 (exact in float32, and exact for the
    scatter's in-segment partial sums) while the running total over all
    edges reaches 2^27: a float32 prefix difference rounds, the int32 one
    must not."""
    seg, ptr = _csr([4] * 8)
    shape = (BATCH, len(seg)) + TRAILING[trailing]
    vals = np.full(shape, 2.0**22 - 1, np.float32)
    nseg = len(ptr) - 1
    S = np.concatenate([np.zeros((1,) + shape[2:], np.float32),
                        np.cumsum(vals[0], axis=0, dtype=np.float32)])
    assert not np.array_equal(S[ptr[1:]] - S[ptr[:-1]],
                              _numpy_sum(vals[0], seg, nseg))
    _check_sum_lowerings(vals, seg, ptr)


def _numpy_extremum(vals, seg, nseg, op):
    pick = np.minimum if op == Q.AGG_MIN else np.maximum
    want = np.full(nseg, np.asarray(SS.minmax_neutral(op)), np.float32)
    for s, v in zip(seg, vals):
        want[s] = pick(want[s], v)
    return want


@pytest.mark.parametrize("op", [Q.AGG_MIN, Q.AGG_MAX])
@pytest.mark.parametrize("case", sorted(SEGMENT_CASES))
def test_deliver_extremum_ptr_matches_numpy_and_scatter(case, op):
    """The segmented scan equals numpy and segment_min/segment_max bit for
    bit, with neutral (liveness-gated) edges mixed in, alone and under a
    batch vmap."""
    seg, ptr = _csr(SEGMENT_CASES[case])
    nseg = len(ptr) - 1
    rng = np.random.default_rng(len(seg) + op)
    vals = rng.integers(-10**6, 10**6, (BATCH, len(seg))).astype(np.float32)
    vals[rng.random(vals.shape) < 0.3] = np.asarray(SS.minmax_neutral(op))
    seg_j, ptr_j = jnp.asarray(seg), jnp.asarray(ptr)
    want = np.stack([_numpy_extremum(v, seg, nseg, op) for v in vals])
    by_ptr = jax.vmap(lambda m: SS.deliver_extremum(m, seg_j, nseg, op,
                                                    ptr=ptr_j))
    by_scatter = jax.vmap(lambda m: SS.deliver_extremum(m, seg_j, nseg, op))
    assert np.array_equal(np.asarray(by_ptr(jnp.asarray(vals))), want)
    assert np.array_equal(np.asarray(by_scatter(jnp.asarray(vals))), want)
    one = SS.deliver_extremum(jnp.asarray(vals[0]), seg_j, nseg, op, ptr=ptr_j)
    assert np.array_equal(np.asarray(one), want[0])


@settings(max_examples=50, deadline=None)
@given(st.lists(st.integers(0, 40), min_size=1, max_size=12), st.data())
def test_deliver_ptr_lowerings_match_numpy(lengths, data):
    """Random CSR layouts (any mix of empty, short and long segments)."""
    seg, ptr = _csr(lengths)
    nseg = len(ptr) - 1
    vals = np.asarray(data.draw(st.lists(
        st.integers(-50, 50), min_size=len(seg), max_size=len(seg))),
        np.float32)
    got = SS.deliver(jnp.asarray(vals), jnp.asarray(seg), nseg,
                     ptr=jnp.asarray(ptr))
    assert np.array_equal(np.asarray(got), _numpy_sum(vals, seg, nseg))
    for op in (Q.AGG_MIN, Q.AGG_MAX):
        got = SS.deliver_extremum(jnp.asarray(vals), jnp.asarray(seg), nseg,
                                  op, ptr=jnp.asarray(ptr))
        assert np.array_equal(np.asarray(got),
                              _numpy_extremum(vals, seg, nseg, op)), op


# -------------------------------------------------------------------------
# the whole hop: gather → apply_edge → deliver, with the extremum channel
# (minmax_edge → deliver_extremum), on the ptr lowering the dense and
# sliced executors run, against the scatter lowering and numpy
# -------------------------------------------------------------------------
HOP_B = 6
HOP_BEDGES = jnp.asarray(iv.bucket_edges(0, 960, HOP_B))
MODES = {"static": SS.MODE_STATIC, "bucket": SS.MODE_BUCKET,
         "interval": SS.MODE_INTERVAL}
AGGS = {"count": None, "min": Q.AGG_MIN, "max": Q.AGG_MAX}


def _hop_problem(kind: str, mode: int, seed: int = 0):
    """One hop with INTEGER counts (the engine's invariant).  ``random``:
    97 vertices, 900 arrival-sorted edges, random sources.  ``sentinel``:
    every fifth edge reads the appended zero row (source ``V``), and no
    edge arrives at the last 10 vertices.  Returns (state rows incl. the
    zero row, t_src, t_dst, ptr, wmask, evalid, extremum channel, V)."""
    rng = np.random.default_rng(seed)
    V, E = (97, 900) if kind == "random" else (40, 50)
    hi = V if kind == "random" else V - 10
    t_dst = np.sort(rng.integers(0, hi, E)).astype(np.int32)
    t_src = rng.integers(0, V, E).astype(np.int32)
    if kind == "sentinel":
        t_src[::5] = V
    ptr = np.searchsorted(t_dst, np.arange(V + 1)).astype(np.int32)
    wmask = rng.random(E) < 0.6
    if mode == SS.MODE_STATIC:
        state, evalid = rng.integers(0, 9, V).astype(np.float32), None
    elif mode == SS.MODE_BUCKET:
        state = rng.integers(0, 9, (V, HOP_B)).astype(np.float32)
        evalid = rng.random((E, HOP_B)) < 0.7
    else:
        state = rng.integers(0, 4, (V, HOP_B, HOP_B + 1)).astype(np.float32)
        evalid = np.sort(rng.integers(0, 960, (E, 2)), axis=1).astype(
            np.int32)
    state = np.concatenate([state, np.zeros((1,) + state.shape[1:],
                                            np.float32)])
    mch = np.concatenate([rng.random(V).astype(np.float32), [0.5]])
    return (state, t_src, t_dst, ptr, wmask, evalid,
            mch.astype(np.float32), V)


def _hop(state, t_src, t_dst, wmask, evalid, mch, V, mode, op, ptr):
    """One hop as the executors run it; ``ptr=None`` is the scatter."""
    with SS.bucket_scope(HOP_BEDGES):
        cnt = SS.apply_edge(state[t_src], wmask, evalid, mode)
        arr = SS.deliver(cnt, t_dst, V, ptr=ptr)
        if op is None:
            return cnt, arr, None
        m_e = SS.minmax_edge(mch[t_src], cnt, op, mode)
        return cnt, arr, SS.deliver_extremum(m_e, t_dst, V, op, ptr=ptr)


def _hop_fns(t_src, t_dst, ptr, V, mode, op):
    ts, td, pj = jnp.asarray(t_src), jnp.asarray(t_dst), jnp.asarray(ptr)
    by_ptr = jax.jit(lambda s, w, e, m: _hop(s, ts, td, w, e, m, V, mode,
                                             op, pj))
    by_scatter = jax.jit(lambda s, w, e, m: _hop(s, ts, td, w, e, m, V, mode,
                                                 op, None))
    return by_ptr, by_scatter


def _numpy_hop(cnt, state, t_src, t_dst, wmask, evalid, mch, V, mode, op):
    """numpy delivery of the per-edge counts ``cnt`` (which it recomputes
    itself in the static and bucket modes) and of the extremum channel."""
    sv = state[t_src]
    if mode == SS.MODE_STATIC:
        np.testing.assert_array_equal(cnt, sv * wmask)
    elif mode == SS.MODE_BUCKET:
        np.testing.assert_array_equal(cnt, sv * (wmask[:, None] & evalid))
    arr = _numpy_sum(cnt, t_dst, V)
    if op is None:
        return arr, None
    alive = cnt.reshape(len(cnt), -1).sum(axis=1) > 0
    m_e = np.where(alive, mch[t_src], np.asarray(SS.minmax_neutral(op)))
    return arr, _numpy_extremum(m_e.astype(np.float32), t_dst, V, op)


@pytest.mark.parametrize("problem", ["random", "sentinel"])
@pytest.mark.parametrize("agg", sorted(AGGS))
@pytest.mark.parametrize("mode", sorted(MODES))
def test_hop_ptr_vs_scatter(mode, agg, problem):
    """Prefix differences and the segmented scan deliver the same hop as
    the segment-sum/min/max scatters and as numpy, bit for bit, per
    temporal mode and aggregate; zero-row sources contribute nothing and
    edgeless destinations get exact zeros and the neutral extremum."""
    md, op = MODES[mode], AGGS[agg]
    state, t_src, t_dst, ptr, wmask, evalid, mch, V = _hop_problem(
        problem, md)
    by_ptr, by_scatter = _hop_fns(t_src, t_dst, ptr, V, md, op)
    args = (jnp.asarray(state), jnp.asarray(wmask),
            None if evalid is None else jnp.asarray(evalid),
            jnp.asarray(mch))
    cnt, arr, mm = (None if x is None else np.asarray(x)
                    for x in by_ptr(*args))
    _, arr_s, mm_s = by_scatter(*args)
    want, want_m = _numpy_hop(cnt, state, t_src, t_dst, wmask, evalid, mch,
                              V, md, op)
    assert np.array_equal(arr, want)
    assert np.array_equal(arr, np.asarray(arr_s))
    if op is not None:
        assert np.array_equal(mm, want_m)
        assert np.array_equal(mm, np.asarray(mm_s))
    if problem == "sentinel":
        assert (cnt[::5] == 0).all()
        assert (arr[V - 10:] == 0).all()
        if op is not None:
            assert (mm[V - 10:] == np.asarray(SS.minmax_neutral(op))).all()


@pytest.mark.parametrize("mode", sorted(MODES))
def test_hop_ptr_batched_pad_rows(mode):
    """Under the executors' batch vmap (8 rows, the last three all-zero
    padding) each row equals its own unbatched hop, and the pad rows
    deliver zeros and the neutral extremum."""
    md, op = MODES[mode], Q.AGG_MIN
    rows = [_hop_problem("random", md, seed=s) for s in range(5)]
    _, t_src, t_dst, ptr, _, _, _, V = rows[0]
    by_ptr, _ = _hop_fns(t_src, t_dst, ptr, V, md, op)
    state = np.stack([r[0] for r in rows] + [np.zeros_like(rows[0][0])] * 3)
    wmask = np.stack([r[4] for r in rows] + [rows[0][4]] * 3)
    evalid = (None if md == SS.MODE_STATIC
              else np.stack([r[5] for r in rows] + [rows[0][5]] * 3))
    mch = np.stack([r[6] for r in rows] + [rows[0][6]] * 3)
    batched = jax.vmap(by_ptr, in_axes=(0, 0, None if evalid is None else 0,
                                        0))
    _, arr, mm = batched(jnp.asarray(state), jnp.asarray(wmask),
                         None if evalid is None else jnp.asarray(evalid),
                         jnp.asarray(mch))
    arr, mm = np.asarray(arr), np.asarray(mm)
    for i in range(8):
        _, a, m = by_ptr(jnp.asarray(state[i]), jnp.asarray(wmask[i]),
                         None if evalid is None else jnp.asarray(evalid[i]),
                         jnp.asarray(mch[i]))
        assert np.array_equal(arr[i], np.asarray(a)), i
        assert np.array_equal(mm[i], np.asarray(m)), i
    assert (arr[5:] == 0).all()
    assert (mm[5:] == np.asarray(SS.minmax_neutral(op))).all()
    assert arr[:5].any()


@pytest.mark.parametrize("channel", ["sum", "extremum"])
@pytest.mark.parametrize("E,V,C", [(1000, 100, 8), (5000, 700, 16),
                                   (300, 512, 4)])
def test_deliver_ptr_width_sweep(E, V, C, channel):
    """The ptr lowerings at wide channel counts and sparse or dense
    segment maps: C columns of per-edge values over E edges sorted into V
    segments, against numpy and the scatter."""
    rng = np.random.default_rng(E + V + C)
    seg = np.sort(rng.integers(0, V, E)).astype(np.int32)
    ptr = np.searchsorted(seg, np.arange(V + 1)).astype(np.int32)
    seg_j, ptr_j = jnp.asarray(seg), jnp.asarray(ptr)
    if channel == "sum":
        vals = rng.integers(0, 50, (E, C)).astype(np.float32)
        got = SS.deliver(jnp.asarray(vals), seg_j, V, ptr=ptr_j)
        scatter = SS.deliver(jnp.asarray(vals), seg_j, V)
        want = _numpy_sum(vals, seg, V)
    else:
        vals = rng.normal(size=(E, C)).astype(np.float32)
        col = lambda p: jax.vmap(  # noqa: E731
            lambda m: SS.deliver_extremum(m, seg_j, V, Q.AGG_MIN, ptr=p),
            in_axes=1, out_axes=1)
        got = col(ptr_j)(jnp.asarray(vals))
        scatter = col(None)(jnp.asarray(vals))
        want = np.stack([_numpy_extremum(vals[:, c], seg, V, Q.AGG_MIN)
                         for c in range(C)], axis=1)
    assert np.array_equal(np.asarray(got), want)
    assert np.array_equal(np.asarray(scatter), want)
