"""Fig 10/11 analogue: per-template average latency, Granite-JAX (planned)
vs no-planner vs single-threaded Python baseline engine (Neo4J-class proxy —
see DESIGN.md §8.3)."""
from __future__ import annotations

import time

import numpy as np

from repro.core import engine as E
from repro.core.ref_engine import RefEngine
from repro.graphdata.ldbc import graph_name
from repro.graphdata.queries import make_workload
from repro.launch.query import GraniteServer
from repro.launch.compile_cache import enable_compile_cache

from .common import N_QUERIES, bench_graphs, emit, get_graph

BASELINE_BUDGET_S = 20.0


def run(aggregate: bool = False):
    for params in bench_graphs():
        g = get_graph(params)
        name = graph_name(params)
        wl = make_workload(g, n_per_template=N_QUERIES, seed=21,
                           aggregate=aggregate)
        server = GraniteServer(g, use_planner=True)
        recs = server.run_workload(wl)
        ref = RefEngine(g, max_expansions=20_000_000)
        by_t = {}
        for inst, rec in zip(wl, recs):
            by_t.setdefault(inst.template, dict(gr=[], base=[], dnf=0))
            by_t[inst.template]["gr"].append(rec.latency_ms)
        # baseline: python enumeration with a budget (first 2 per template)
        done = {}
        for inst in wl:
            k = inst.template
            if done.get(k, 0) >= 2:
                continue
            done[k] = done.get(k, 0) + 1
            t0 = time.perf_counter()
            try:
                if aggregate:
                    ref.aggregate(inst.qry, mode=E.MODE_STATIC)
                else:
                    ref.count(inst.qry, mode=E.MODE_STATIC)
                dt = (time.perf_counter() - t0) * 1e3
                if dt > BASELINE_BUDGET_S * 1e3:
                    by_t[k]["dnf"] += 1
                else:
                    by_t[k]["base"].append(dt)
            except RuntimeError:
                by_t[k]["dnf"] += 1
        tag = "agg" if aggregate else "nonagg"
        for t, d in sorted(by_t.items()):
            gr = np.mean(d["gr"])
            base = np.mean(d["base"]) if d["base"] else float("nan")
            speedup = base / gr if d["base"] else float("nan")
            emit(f"latency_{tag}/{name}/{t}", gr * 1e3,
                 f"baseline_ms={base:.1f};speedup={speedup:.1f}x;dnf={d['dnf']}")


def main():
    enable_compile_cache()
    run(aggregate=False)


if __name__ == "__main__":
    main()
