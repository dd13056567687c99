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
