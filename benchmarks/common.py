"""Shared benchmark utilities: graph cache, timing, CSV emission."""
from __future__ import annotations

import os
import sys
import time
from typing import Dict, List

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.graphdata.ldbc import LdbcParams, generate_ldbc, graph_name

# BENCH_SCALE=full uses larger graphs (minutes); default is CI-sized.
SCALE = os.environ.get("BENCH_SCALE", "ci")
N_PERSONS = {"ci": 400, "full": 2000}[SCALE]
N_QUERIES = {"ci": 5, "full": 25}[SCALE]

_GRAPH_CACHE: Dict[str, object] = {}

ROWS: List[str] = []


def bench_graphs(dists=("facebook", "zipf"), dynamic_too=True):
    out = []
    for dist in dists:
        p = LdbcParams(n_persons=N_PERSONS, degree_dist=dist, dynamic=False, seed=2)
        out.append(p)
        if dynamic_too:
            out.append(LdbcParams(n_persons=N_PERSONS // 2, degree_dist=dist,
                                  dynamic=True, seed=2))
    return out


def get_graph(params: LdbcParams):
    key = graph_name(params)
    if key not in _GRAPH_CACHE:
        _GRAPH_CACHE[key] = generate_ldbc(params)
    return _GRAPH_CACHE[key]


def emit(name: str, us_per_call: float, derived: str = ""):
    row = f"{name},{us_per_call:.1f},{derived}"
    ROWS.append(row)
    print(row, flush=True)


def timeit(fn, repeat: int = 3, warmup: int = 1) -> float:
    for _ in range(warmup):
        fn()
    t0 = time.perf_counter()
    for _ in range(repeat):
        fn()
    return (time.perf_counter() - t0) / repeat * 1e6   # µs
