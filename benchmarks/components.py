"""Fig 13 analogue: component times per superstep (init/compute, scatter,
delivery, ETR) measured with an instrumented eager runner."""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import engine as E
from repro.core import query as Q
from repro.graphdata.ldbc import graph_name
from repro.graphdata.queries import make_workload
from repro.launch.compile_cache import enable_compile_cache

from .common import bench_graphs, emit, get_graph


def _timed(fn, *a):
    t0 = time.perf_counter()
    out = fn(*a)
    jax.block_until_ready(out)
    return out, (time.perf_counter() - t0) * 1e3


def component_times(g, qry: Q.PathQuery) -> dict:
    """Eager per-phase timing of a left-to-right execution."""
    gdev = E._prepare_gdev(g)
    import repro.core.intervals as iv
    bedges = jnp.asarray(iv.bucket_edges(g.lifespan[0], g.lifespan[1], 16))
    E._TRACE_BEDGES.append(None)
    pv, pe = E._pbases(qry)
    params = jnp.asarray(Q.query_params(qry))
    phases = {}
    try:
        V = gdev["v_life"].shape[0]
        # init
        (vm, vv), t = _timed(
            E._eval_predicate, gdev["vprops"], gdev["v_type"], gdev["v_life"],
            qry.v_preds[0].vtype, qry.v_preds[0].clauses, params, pv[0], 0, None)
        phases["init"] = t
        state = vm.astype(jnp.float32)
        prev_raw = None
        for i, ep in enumerate(qry.e_preds):
            (wmask, _), t_s = _timed(
                E._edge_predicate_weights, gdev, ep, params, pe[i], 0, None)
            if i > 0:
                (vm, vv), t_c = _timed(
                    E._eval_predicate, gdev["vprops"], gdev["v_type"],
                    gdev["v_life"], qry.v_preds[i].vtype, qry.v_preds[i].clauses,
                    params, pv[i], 0, None)
                phases[f"compute_{i}"] = t_c
            if ep.etr_op != -1 and prev_raw is not None:
                src_cnt, t_etr = _timed(
                    E._etr_weighted, gdev, prev_raw, ep.etr_op, False, False)
                phases[f"etr_{i}"] = t_etr
                src_val = src_cnt * vm[gdev["t_src"]].astype(jnp.float32)
            else:
                sv = state if i == 0 else arrivals * vm.astype(jnp.float32)
                src_val = sv[gdev["t_src"]]
            cnt_e = src_val * wmask.astype(jnp.float32)
            phases[f"scatter_{i}"] = t_s
            (arrivals,), t_d = _timed(
                lambda c: (jax.ops.segment_sum(c, gdev["t_dst"], num_segments=V,
                                               indices_are_sorted=True),), cnt_e)
            phases[f"deliver_{i}"] = t_d
            prev_raw = cnt_e
    finally:
        E._TRACE_BEDGES.pop()
    return phases


def run():
    params = bench_graphs(dynamic_too=False)[0]
    g = get_graph(params)
    name = graph_name(params)
    wl = make_workload(g, templates=("Q7", "Q3"), n_per_template=2, seed=30)
    for inst in wl[::2]:
        ph = component_times(g, inst.qry)
        total = sum(ph.values())
        detail = ";".join(f"{k}={v:.2f}ms" for k, v in ph.items())
        emit(f"components/{name}/{inst.template}", total * 1e3, detail)


def main():
    enable_compile_cache()
    run()


if __name__ == "__main__":
    main()
