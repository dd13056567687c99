"""Fig 8/9 + Table 6 analogue: cost-model plan-choice quality.

For each query: execute EVERY split plan, find the optimal by measured time,
compare the model's choice; report %optimal / %2nd-best / %other and the
excess-time-over-optimal percentiles per template.
"""
from __future__ import annotations

import time

import numpy as np

from repro.core import engine as E
from repro.core.planner import Planner
from repro.core.stats import GraphStats
from repro.graphdata.ldbc import graph_name
from repro.graphdata.queries import make_workload
from repro.launch.compile_cache import enable_compile_cache

from .common import N_QUERIES, bench_graphs, emit, get_graph


def _measure(g, qry, split, repeat=3):
    E.count_results(g, qry, split=split)  # warm/compile
    t0 = time.perf_counter()
    for _ in range(repeat):
        E.count_results(g, qry, split=split)
    return (time.perf_counter() - t0) / repeat * 1e3


def run():
    for params in bench_graphs(dynamic_too=False):
        g = get_graph(params)
        name = graph_name(params)
        stats = GraphStats(g)
        planner = Planner(g, stats)
        wl = make_workload(g, n_per_template=N_QUERIES, seed=22)
        picked_rank = []
        excess = {}
        by_template_excess = {}
        for inst in wl:
            times = {s: _measure(g, inst.qry, s)
                     for s in range(inst.qry.n_vertices)}
            order = sorted(times, key=times.get)
            chosen = planner.choose(inst.qry).split
            picked_rank.append(order.index(chosen))
            exc = (times[chosen] - times[order[0]]) / max(times[order[0]], 1e-9)
            by_template_excess.setdefault(inst.template, []).append(exc * 100)
        ranks = np.asarray(picked_rank)
        emit(f"cost_model/{name}/plan_choice", 0.0,
             f"optimal={np.mean(ranks == 0)*100:.0f}%;"
             f"second={np.mean(ranks == 1)*100:.0f}%;"
             f"other={np.mean(ranks >= 2)*100:.0f}%")
        for t, ex in sorted(by_template_excess.items()):
            ex = np.asarray(ex)
            emit(f"cost_model/{name}/excess/{t}", 0.0,
                 f"p50={np.percentile(ex,50):.1f}%;p90={np.percentile(ex,90):.1f}%;"
                 f"p95={np.percentile(ex,95):.1f}%")


def main():
    enable_compile_cache()
    run()


if __name__ == "__main__":
    main()
