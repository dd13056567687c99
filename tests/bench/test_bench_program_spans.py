"""The readers of the scheduler's dispatch stamps, on hand-built flushes:
``flush_wait_ms_p50`` and ``host_gap_ms_p50``."""
import os
import sys
from types import SimpleNamespace

import pytest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
sys.path.insert(0, ROOT)

from bench import harness  # noqa: E402
from bench.pump import FlushRecord  # noqa: E402
from repro.serving.scheduler import GroupDispatch  # noqa: E402


def unit(n_real, start, launch, ready, end):
    """One dispatch with its stamps given in ms."""
    return GroupDispatch(
        key=(), engine="dense", split=0, n_real=n_real, n_pad=0,
        service_s=(ready - launch) * 1e-3, indices=list(range(n_real)),
        plan_cached=True, exec_cached=True, t_start=start * 1e-3,
        t_launch=launch * 1e-3, t_ready=ready * 1e-3, t_end=end * 1e-3)


def window():
    """Flush A: three units, the middle one serving three queries.  Flush
    B: one unit alone, which waits on nothing."""
    a = [unit(1, 0, 1, 301, 302), unit(3, 302, 305, 505, 506),
         unit(1, 506, 508, 808, 810)]
    b = [unit(1, 1000, 1002, 1202, 1203)]
    return SimpleNamespace(flushes=[
        FlushRecord(0.0, 0.811, [0, 1, 2, 3, 4], a),
        FlushRecord(0.999, 1.204, [5], b)])


def test_flush_wait_weights_each_unit_by_its_queries():
    # A: first start 0, last end 810.  Unit 1 waits 0 + (810 - 302) = 508,
    # unit 2 302 + (810 - 506) = 606 (three times), unit 3 506 + 0 = 506;
    # B's unit 0.  Median of [0, 506, 508, 606, 606, 606] = 557.
    got = harness.reader("flush_wait_ms_p50")(window())
    assert got == pytest.approx(557.0)


def test_host_gap_is_ready_to_next_launch_within_a_flush():
    # A: 305 - 301 = 4 and 508 - 505 = 3; B has one unit, so no gap, and
    # no gap spans two flushes.  Median of [4, 3] = 3.5.
    assert harness.reader("host_gap_ms_p50")(window()) == pytest.approx(3.5)


@pytest.mark.parametrize("name", ["flush_wait_ms_p50", "host_gap_ms_p50"])
def test_no_dispatch_reads_nothing(name):
    read = harness.reader(name)
    assert read(SimpleNamespace(flushes=[])) is None
    assert read(SimpleNamespace(flushes=[FlushRecord(0.0, 1.0, [], [])])) \
        is None


@pytest.mark.parametrize("name", ["flush_wait_ms_p50", "host_gap_ms_p50"])
def test_dispatches_without_stamps_read_nothing(name):
    """A scheduler that stamps nothing (an older program) gives no
    reading, and no error."""
    bare = SimpleNamespace(n_real=1, n_pad=0, service_s=0.3)
    ctx = SimpleNamespace(flushes=[FlushRecord(0.0, 1.0, [0, 1],
                                               [bare, bare])])
    assert harness.reader(name)(ctx) is None
