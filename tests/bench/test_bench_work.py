"""Hop operations and bytes against counts made by hand."""
import os
import sys

import pytest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
sys.path.insert(0, ROOT)

from bench import work  # noqa: E402


@pytest.mark.parametrize("width", [1, 16])
def test_hop_work_by_hand(width):
    # 5 vertices, 3 edges -> 6 traversal edges; 2 hops for 3 real queries.
    # Per hop: 6 edges x 16 B of structure once, and per query 6 gathers and
    # 5 writes of `width` float32; one add per traversal edge and element.
    ops, nbytes = work.hop_work(5, 3, width, hops=2, batch=3)
    assert nbytes == 2 * (6 * 16 + 3 * (6 + 5) * width * 4)
    assert ops == 2 * 3 * 6 * width


def test_padding_is_not_work():
    one = work.hop_work(10, 7, 1, 3, 1)
    two = work.hop_work(10, 7, 1, 3, 2)
    assert two[0] == 2 * one[0]
    assert two[1] - one[1] == 3 * (14 + 10) * 4


def test_least_time_takes_the_binding_roof():
    peak = dict(flops_per_s=100.0, hbm_bytes_per_s=10.0)
    assert work.least_time_s(1000, 50, peak) == 10.0
    assert work.least_time_s(100, 500, peak) == 50.0
