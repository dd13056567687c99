"""Interval algebra unit + property tests."""
import jax.numpy as jnp
import numpy as np
import pytest

hypothesis = pytest.importorskip(
    "hypothesis", reason="property tests need the optional hypothesis dep "
    "(pip install -r requirements-dev.txt)")
from hypothesis import given, settings  # noqa: E402
from hypothesis import strategies as st  # noqa: E402

from repro.core import intervals as iv


def brute(op, a, b):
    (a_s, a_e), (b_s, b_e) = a, b
    if a_s >= a_e or b_s >= b_e:
        return False
    return {
        iv.FULLY_BEFORE: a_e <= b_s,
        iv.STARTS_BEFORE: a_s < b_s,
        iv.FULLY_AFTER: a_s >= b_e,
        iv.STARTS_AFTER: a_s > b_s,
        iv.DURING: a_s > b_s and a_e < b_e,
        iv.EQUALS: (a_s, a_e) == (b_s, b_e),
        iv.DURING_EQ: a_s >= b_s and a_e <= b_e,
        iv.OVERLAPS: a_s < b_e and b_s < a_e,
    }[op]


ivs = st.tuples(st.integers(0, 50), st.integers(0, 50))


@settings(max_examples=200, deadline=None)
@given(a=ivs, b=ivs, op=st.sampled_from(list(range(8))))
def test_compare_matches_bruteforce(a, b, op):
    got = bool(iv.compare(op, jnp.asarray(a), jnp.asarray(b)))
    assert got == brute(op, a, b)


def test_intersect_and_empty():
    a = jnp.asarray([[0, 10], [5, 8], [0, 3]])
    b = jnp.asarray([[5, 15], [0, 20], [3, 9]])
    out = iv.intersect(a, b)
    np.testing.assert_array_equal(np.asarray(out), [[5, 10], [5, 8], [3, 3]])
    assert bool(iv.is_empty(out[2])) and not bool(iv.is_empty(out[0]))


@settings(max_examples=100, deadline=None)
@given(a=ivs, b=ivs)
def test_overlaps_symmetric_and_consistent_with_intersect(a, b):
    ja, jb = jnp.asarray(a), jnp.asarray(b)
    ov = bool(iv.overlaps(ja, jb))
    assert ov == bool(iv.overlaps(jb, ja))
    inter = iv.intersect(ja, jb)
    valid = a[0] < a[1] and b[0] < b[1]
    assert ov == (valid and not bool(iv.is_empty(inter)))


def test_bucket_mask_exact_on_aligned():
    edges = iv.bucket_edges(0, 160, 16)
    assert edges[0] == 0 and edges[-1] >= 160
    m = iv.interval_to_bucket_mask(jnp.asarray([10, 30]), jnp.asarray(edges))
    width = edges[1] - edges[0]
    got = np.nonzero(np.asarray(m))[0]
    assert got.min() == 10 // width and got.max() == (30 - 1) // width


@settings(max_examples=100, deadline=None)
@given(s=st.integers(0, 99), e=st.integers(1, 100), B=st.sampled_from([4, 8, 16]))
def test_bucket_mask_covers_interval(s, e, B):
    if s >= e:
        return
    edges = iv.bucket_edges(0, 100, B)
    m = np.asarray(iv.interval_to_bucket_mask(jnp.asarray([s, e]),
                                              jnp.asarray(edges)))
    for b in range(B):
        expect = (s < edges[b + 1]) and (edges[b] < e)
        assert m[b] == expect


@pytest.mark.parametrize("N,B", [(512, 8), (3000, 16), (100, 32)])
def test_interval_bucket_mask_sweep(N, B):
    """Counts times the bucket mask keep exactly the buckets an interval
    overlaps (``s < hi and lo < e``), over random and inverted intervals;
    where the interval is non-empty that is the exact intersection test."""
    rng = np.random.default_rng(N + B)
    counts = rng.normal(size=(N, B)).astype(np.float32)
    ivl = np.stack([rng.integers(0, 500, N), rng.integers(0, 1100, N)],
                   1).astype(np.int32)
    edges = iv.bucket_edges(0, 1096, B)
    got = np.asarray(jnp.asarray(counts) * iv.interval_to_bucket_mask(
        jnp.asarray(ivl), jnp.asarray(edges)))
    lo, hi = edges[:-1][None, :], edges[1:][None, :]
    s, e = ivl[:, :1], ivl[:, 1:]
    overlap = (s < hi) & (lo < e)
    np.testing.assert_array_equal(got, counts * overlap)
    nonempty = ivl[:, 0] < ivl[:, 1]
    exact = np.maximum(s, lo) < np.minimum(e, hi)
    np.testing.assert_array_equal(overlap[nonempty], exact[nonempty])
