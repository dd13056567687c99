"""Table 7 analogue: % of workload completing within the execution budget.

Granite-JAX vs the single-threaded Python baseline engine; the budget scales
the paper's 600 s to bench size.
"""
from __future__ import annotations

import time

from repro.core import engine as E
from repro.core.ref_engine import RefEngine
from repro.graphdata.ldbc import graph_name
from repro.graphdata.queries import make_workload
from repro.launch.query import GraniteServer
from repro.launch.compile_cache import enable_compile_cache

from .common import N_QUERIES, bench_graphs, emit, get_graph

BUDGET_S = 5.0


def run():
    for params in bench_graphs():
        g = get_graph(params)
        name = graph_name(params)
        wl = make_workload(g, n_per_template=max(2, N_QUERIES // 2), seed=51)
        server = GraniteServer(g, budget_s=BUDGET_S)
        recs = server.run_workload(wl)
        g_done = sum(r.ok for r in recs)
        ref = RefEngine(g, max_expansions=2_000_000)
        b_done = 0
        n_base = 0
        for inst in wl[:: max(1, len(wl) // 8)]:
            n_base += 1
            t0 = time.perf_counter()
            try:
                ref.count(inst.qry, mode=E.MODE_STATIC)
                if time.perf_counter() - t0 <= BUDGET_S:
                    b_done += 1
            except RuntimeError:
                pass
        emit(f"completion/{name}", 0.0,
             f"granite={g_done}/{len(recs)};baseline={b_done}/{n_base}")


def main():
    enable_compile_cache()
    run()


if __name__ == "__main__":
    main()
