"""The trace reduction: busy-interval union, idle share, gap attribution,
on hand-made events and on a small trace recorded on a TPU v5e."""
import json
import os
import sys

import pytest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
sys.path.insert(0, ROOT)

from bench import trace_reduce as T  # noqa: E402

DEV = "/device:TPU:0"


def test_union_merges_overlaps_and_drops_empty():
    assert T.union([(15, 30), (10, 20), (50, 60), (60, 61), (5, 5)]) == [
        (10, 30), (50, 61)]


def test_gaps_cover_the_rest_of_the_window():
    assert T.gaps([(10, 30), (50, 60)], 0, 100) == [(0, 10), (30, 50),
                                                    (60, 100)]
    assert T.gaps([], 0, 5) == [(0, 5)]


def test_reduce_hand_made():
    ev = dict(devices={DEV: [("a", 10, 20), ("b", 15, 30), ("c", 50, 60),
                             ("d", 100, 120)]},
              spans=[("bench.submit", 0, 10), ("bench.flush", 10, 70),
                     ("bench.wait_arrival", 70, 100)])
    r = T.reduce(ev)
    assert r["window_s"] == pytest.approx(100e-9)
    assert r["busy_s"] == pytest.approx(30e-9)       # (10,30) + (50,60)
    assert r["idle_share"] == pytest.approx(0.7)
    assert [n for n, _ in r["idle_gaps"]] == [
        "bench.wait_arrival", "bench.flush", "bench.submit"]
    assert [s for _, s in r["idle_gaps"]] == pytest.approx(
        [40e-9, 20e-9, 10e-9])
    assert r["device_ops"][0] == ["b", pytest.approx(15e-9)]
    assert {n for n, _ in r["device_ops"]} == {"a", "b", "c"}


def test_busy_is_averaged_over_devices():
    ev = dict(devices={DEV: [("a", 0, 50)], "/device:TPU:1": [("a", 0, 10)]},
              spans=[("bench.flush", 0, 100)])
    assert T.reduce(ev)["busy_s"] == pytest.approx(30e-9)


def test_op_names_carry_their_program():
    ops = [("%fusion.2 = f32[] fusion(x)", 5, 9), ("%copy = f32[]", 20, 21)]
    mods = [("jit_one(1672)", 4, 10)]
    assert T.name_ops(ops, mods) == [("jit_one/fusion.2", 5, 9),
                                     ("copy", 20, 21)]


def test_reduce_recorded_v5e_trace():
    """Three fake flushes of two matmuls each, traced on one TPU v5e."""
    with open(os.path.join(os.path.dirname(__file__), "data",
                           "trace_small.json")) as f:
        ev = json.load(f)
    r = T.reduce(ev)
    spans = ev["spans"]
    lo = min(a for _, a, _ in spans)
    hi = max(b for _, _, b in spans)
    ops = ev["devices"][DEV]
    assert len(ops) == 6
    assert r["window_s"] == pytest.approx((hi - lo) * 1e-9)
    assert r["busy_s"] == pytest.approx(sum(b - a for _, a, b in ops) * 1e-9)
    assert 0 < r["idle_share"] < 1
    names = {n for n, _ in r["idle_gaps"]}
    assert names <= {"bench.submit", "bench.flush", "bench.wait_arrival"}
    assert r["idle_gaps"][0][0] == "bench.wait_arrival"
