"""Fig 14 analogue: weak scaling — graph size ∝ worker count.

Unlike the seed (which only *modelled* per-worker makespan from partition
edge extents), this executes the PARTITIONED engine for real: each worker's
local superstep (halo gather → edge apply → local segment-sum delivery) is
run and timed separately (engine_partitioned.measure_supersteps), so the
reported quantities are measured wall-clock:

  * makespan: Σ_hops max_w t[hop, w] — the straggler-bound superstep time a
    BSP deployment would see (the paper's Q3/Q4 straggler effect);
  * balance_eff: mean worker time / max worker time (load-balance component);
  * weak_eff: w=2-relative per-edge makespan throughput × balance
    (perfect weak scaling ⇒ flat makespan per edge);
  * exchange: measured PER-CHANNEL boundary volume per query on the
    point-to-point lanes (state/extremum = halo ghosts, ETR = boundary rank
    summaries — cut edges), exactly the columns θ_net / θ_net_etr are fitted
    on (benchmarks/fit_cost_model) — keeping the cost model's accuracy claim
    checkable against the executor's real traffic.  The workload includes a
    MIN leg (queries.to_minmax) so the extremum channel is EXERCISED, not
    structurally zero — all three channels carry measured volume.

Writes ``BENCH_weak_scaling.json`` (per-worker-count rows); the CI bench
gate (scripts/check_bench.py) pins the structural exchange volumes exactly
and the efficiency ratios within a tolerance band.
"""
from __future__ import annotations

import json

import numpy as np

from repro.core import engine_partitioned as EP
from repro.graphdata.ldbc import LdbcParams, generate_ldbc
from repro.graphdata.queries import make_workload, to_minmax
from repro.launch.compile_cache import enable_compile_cache

from .common import SCALE, emit

BASE = {"ci": 50, "full": 125}[SCALE]
WORKERS = {"ci": (2, 4, 8), "full": (2, 4, 8, 16)}[SCALE]


def run(out_path: str = "BENCH_weak_scaling.json") -> dict:
    rows = []
    ref = None
    for w in WORKERS:
        params = LdbcParams(n_persons=BASE * w, degree_dist="facebook", seed=3)
        g = generate_ldbc(params)
        part, arrays, _ = EP.partition_for(g, w, max(4, w // 2))
        wl = make_workload(g, templates=("Q1", "Q2", "Q4"), n_per_template=3,
                           seed=31)
        # a MIN variant of a Q2 instance: the extremum channel carries real
        # boundary volume (without it the channel is structurally zero and
        # the gate on it is vacuous)
        q2 = next(i for i in wl if i.template == "Q2")
        wl = wl + [to_minmax(q2, g)]
        makespans, worker_time = [], np.zeros(w)
        channels = np.zeros(len(EP.CHANNELS), np.int64)
        for inst in wl:
            # repeats>1 takes the min per (hop, worker), excluding compile time
            prof = EP.measure_supersteps(g, inst.qry, n_workers=w, repeats=2)
            makespans.append(prof.makespan_s.sum())
            worker_time += prof.times_s.sum(axis=0)
            channels += prof.exchange_channels.sum(axis=0)
        makespan = float(np.mean(makespans))           # s per query, measured
        balance_eff = float(worker_time.mean() / max(worker_time.max(), 1e-12))
        per_edge = makespan / max(g.n_edges, 1)
        if ref is None:
            ref = per_edge
        weak_eff = min(1.0, (ref / per_edge)) * balance_eff
        xchg = {name: int(channels[i]) // len(wl)
                for i, name in enumerate(EP.CHANNELS)}
        rows.append(dict(
            n_workers=w,
            n_persons=BASE * w,
            n_edges=int(g.n_edges),
            makespan_s=makespan,
            balance_eff=balance_eff,
            weak_eff=weak_eff,
            edge_cut=float(part.stats["edge_cut"]),
            exchange_per_query=xchg,
            exchange_volume=arrays.exchange_volume(),
            etr_exchange_volume=arrays.etr_exchange_volume(),
        ))
        emit(f"weak_scaling/w{w}", makespan * 1e6,
             f"persons={BASE*w};balance_eff={balance_eff*100:.0f}%;"
             f"weak_eff={weak_eff*100:.0f}%;edge_cut={part.stats['edge_cut']*100:.1f}%;"
             f"xchg_state={xchg['state']};xchg_extremum={xchg['extremum']};"
             f"xchg_etr={xchg['etr']}")
    report = dict(scale=SCALE, base_persons=BASE, rows=rows)
    with open(out_path, "w") as f:
        json.dump(report, f, indent=2)
    print(f"# wrote {out_path}", flush=True)
    return report


def main():
    enable_compile_cache()
    run()


if __name__ == "__main__":
    main()
