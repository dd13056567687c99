"""Operations and bytes of hop delivery, from shapes alone.

A hop moves a path count along every traversal edge (each edge, both
ways: ``2E``) into its arrival vertex.  The least it must move, whatever
the lowering:

* per hop, once for the whole batch: each traversal edge's endpoint
  (int32), type (int32) and lifespan (two int32);
* per hop and real query: the source state gathered per traversal edge and
  the arrival state written per vertex, ``width`` float32 each (width 1 in
  static mode, ``n_buckets`` in bucket mode);

and it adds one number per traversal edge and state element.  Only real
queries count: padding rows are waste.
"""
from __future__ import annotations

EDGE_BYTES = 4 + 4 + 2 * 4
STATE_BYTES = 4


def hop_work(n_vertices: int, n_edges: int, width: int, hops: int,
             batch: int) -> tuple:
    """(operations, bytes) of ``hops`` hops for ``batch`` real queries."""
    t = 2 * n_edges
    per_query = (t + n_vertices) * width * STATE_BYTES
    nbytes = hops * (t * EDGE_BYTES + batch * per_query)
    ops = hops * batch * t * width
    return ops, nbytes


def least_time_s(ops: float, nbytes: float, peak: dict) -> float:
    """The roofline: the larger of operations over peak rate and bytes
    over peak bandwidth."""
    return max(ops / peak["flops_per_s"], nbytes / peak["hbm_bytes_per_s"])
