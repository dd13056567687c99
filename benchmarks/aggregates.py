"""Fig 12 analogue: temporal-aggregate query latency vs non-aggregate."""
from __future__ import annotations

import numpy as np

from repro.graphdata.ldbc import graph_name
from repro.graphdata.queries import make_workload
from repro.launch.query import GraniteServer
from repro.launch.compile_cache import enable_compile_cache

from .common import N_QUERIES, bench_graphs, emit, get_graph


def run():
    for params in bench_graphs(dists=("facebook",)):
        g = get_graph(params)
        name = graph_name(params)
        server = GraniteServer(g)
        wl_plain = make_workload(g, n_per_template=N_QUERIES, seed=41)
        wl_agg = make_workload(g, n_per_template=N_QUERIES, seed=41,
                               aggregate=True)
        r_plain = server.run_workload(wl_plain)
        r_agg = server.run_workload(wl_agg)
        by_t = {}
        for inst, rp in zip(wl_plain, r_plain):
            by_t.setdefault(inst.template, [[], []])[0].append(rp.latency_ms)
        for inst, ra in zip(wl_agg, r_agg):
            by_t.setdefault(inst.template, [[], []])[1].append(ra.latency_ms)
        for t, (pl, ag) in sorted(by_t.items()):
            if not pl or not ag:
                continue
            emit(f"aggregates/{name}/{t}", np.mean(ag) * 1e3,
                 f"plain_ms={np.mean(pl):.2f};agg_ms={np.mean(ag):.2f};"
                 f"overhead={np.mean(ag)/max(np.mean(pl),1e-9)*100-100:.0f}%")


def main():
    enable_compile_cache()
    run()


if __name__ == "__main__":
    main()
