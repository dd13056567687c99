"""The reader of the scheduler's extent record, ``extent_query_share``, on
hand-built flushes of ``GroupDispatch`` rows."""
import os
import sys
from types import SimpleNamespace

import pytest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
sys.path.insert(0, ROOT)

from bench import harness  # noqa: E402
from bench.pump import FlushRecord  # noqa: E402
from repro.serving.scheduler import GroupDispatch  # noqa: E402

READ = harness.reader("extent_query_share")


def unit(n_real, extents, n_pad=0):
    return GroupDispatch(
        key=(), engine="dense", split=0, n_real=n_real, n_pad=n_pad,
        service_s=0.1, indices=list(range(n_real)), plan_cached=True,
        exec_cached=True, extents=extents)


def test_mixed_rows_give_the_real_query_share():
    # 3 + 2 + 1 real queries on extents, 2 on the whole graph; padding
    # rows are not queries.  6 of 8.
    ctx = SimpleNamespace(flushes=[
        FlushRecord(0.0, 1.0, list(range(5)),
                    [unit(3, True, n_pad=1), unit(2, False)]),
        FlushRecord(1.0, 2.0, list(range(3)),
                    [unit(2, True, n_pad=2), unit(1, True)])])
    assert READ(ctx) == pytest.approx(75.0)


@pytest.mark.parametrize("extents,want", [(True, 100.0), (False, 0.0)])
def test_one_kind_of_row(extents, want):
    ctx = SimpleNamespace(flushes=[
        FlushRecord(0.0, 1.0, [0, 1], [unit(2, extents)])])
    assert READ(ctx) == want


def test_no_rows_read_nothing():
    assert READ(SimpleNamespace(flushes=[])) is None
    assert READ(SimpleNamespace(flushes=[FlushRecord(0.0, 1.0, [], [])])) \
        is None


def test_rows_without_the_record_read_nothing():
    """A scheduler that does not record extents (an older program) gives
    no reading, and no error."""
    bare = SimpleNamespace(n_real=1, n_pad=0, service_s=0.3)
    ctx = SimpleNamespace(flushes=[FlushRecord(0.0, 1.0, [0, 1],
                                               [bare, bare])])
    assert READ(ctx) is None
