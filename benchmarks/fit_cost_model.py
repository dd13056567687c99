"""Micro-benchmark fit of the cost-model execution-time coefficients
(paper Table 3 analogue) — writes src/repro/configs/cost_coeffs.json.

Features per measured superstep batch:
  [1, V_slice, E_slice, etr·E_slice, m̄, m_net_state, m_net_etr]
where the first five come from dense single-stream runs (exchange columns 0)
and the two PER-CHANNEL exchange columns come from MEASURED partitioned
supersteps (engine_partitioned.measure_supersteps): per-worker compute
extents divide by the worker count, and the boundary volumes are the ragged
point-to-point lane contents the executor actually moves — halo ghost
entries for the vertex-state channel (the MIN/MAX extremum channel rides
the same lanes, so its rows double the state column), boundary rank
summaries (cut edges) for the ETR channel.  The fitted θ_net / θ_net_etr
pair makes plan selection distribution-aware per channel.
"""
from __future__ import annotations

import time

import numpy as np

from repro.core import engine as E
from repro.core import engine_partitioned as EP
from repro.core import query as Q
from repro.core.planner import fit_linear, load_coeffs, save_coeffs
from repro.core.stats import GraphStats
from repro.graphdata.ldbc import LdbcParams, generate_ldbc
from repro.graphdata.queries import make_workload
from repro.launch.compile_cache import enable_compile_cache

from .common import SCALE, emit


def _trav_by_type(g):
    """Traversal arrivals per vertex type (same derivation as Planner)."""
    deg = g.in_degree.astype(np.int64) + g.out_degree.astype(np.int64)
    out = np.zeros(g.n_vertex_types, np.int64)
    np.add.at(out, g.v_type, deg)
    return out


def _step_features(g, qry, trav_by_type, V, E2):
    """Per-superstep (v_slice, e_slice, etr) extents for a query's hops."""
    n_steps = qry.n_vertices
    v_slices, e_slices, etrs = [], [], []
    for i, vp in enumerate(qry.v_preds):
        v_slices.append(g.type_counts[vp.vtype] if vp.vtype >= 0 else V)
        nxt = qry.v_preds[i + 1].vtype if i + 1 < n_steps else -1
        e_slices.append(trav_by_type[nxt] if nxt >= 0 else E2)
        etrs.append(1.0 if (i < len(qry.e_preds) and
                            qry.e_preds[i].etr_op != -1) else 0.0)
    return np.asarray(v_slices, float), np.asarray(e_slices, float), np.asarray(etrs)


def run(write: bool = True):
    sizes = {"ci": (150, 400), "full": (400, 1200)}[SCALE]
    part_workers = {"ci": (2, 4), "full": (2, 4, 8)}[SCALE]
    rows, times = [], []
    graphs = []
    for n in sizes:
        g = generate_ldbc(LdbcParams(n_persons=n, degree_dist="facebook", seed=6))
        graphs.append(g)
        V, E2 = g.n_vertices, 2 * g.n_edges
        trav_by_type = _trav_by_type(g)
        wl = make_workload(g, n_per_template=3, seed=61)
        for inst in wl:
            qry = inst.qry
            for split in (0, qry.n_vertices - 1):
                E.count_results(g, qry, split=split)  # compile
                t0 = time.perf_counter()
                for _ in range(3):
                    out = E.execute(g, qry, split=split)
                t = (time.perf_counter() - t0) / 3 * 1e3
                v_s, e_s, etrs = _step_features(g, qry, trav_by_type, V, E2)
                feats = np.asarray([
                    qry.n_vertices,
                    float(np.sum(v_s)),
                    float(np.sum(e_s[:-1])),
                    float(np.sum(etrs[:-1] * e_s[:-1])),
                    float(np.sum(e_s[:-1])) * 0.05,  # message proxy
                    0.0,                             # no exchange single-stream
                    0.0,
                ])
                rows.append(feats)
                times.append(t)

    # ---- partitioned supersteps: measured per-worker makespans + the
    # per-channel ragged exchange volumes (state incl. extremum, ETR)
    g = graphs[0]
    V, E2 = g.n_vertices, 2 * g.n_edges
    trav_by_type = _trav_by_type(g)
    wl = make_workload(g, templates=("Q1", "Q2", "Q4"), n_per_template=2, seed=62)
    # a MIN/MAX instance so the extremum channel (state lanes ×2) is in the
    # fitted population, not just modelled — same construction the serving
    # bench and the multidevice tests use (queries.to_minmax)
    from repro.graphdata.queries import to_minmax
    qmm = to_minmax(
        make_workload(g, templates=("Q2",), n_per_template=1, seed=63)[0],
        g).qry
    queries = [inst.qry for inst in wl] + [qmm]
    for w in part_workers:
        for qry in queries:
            prof = EP.measure_supersteps(g, qry, n_workers=w, repeats=2)
            t = float(prof.makespan_s.sum()) * 1e3  # ms, straggler per hop
            fq = qry.reversed() if qry.agg_op != Q.AGG_NONE else qry
            v_s, e_s, etrs = _step_features(g, fq, trav_by_type, V, E2)
            ch = prof.channel_totals()
            # features must describe what measure_supersteps TIMES: one
            # dispatch per hop of local compute (edge apply + delivery +
            # halo gather; on ETR hops also the per-worker rank-summary
            # prefix tables) — init predicate eval and the final join are
            # untimed there, so those columns are zeroed for these rows.
            feats = np.asarray([
                len(qry.e_preds),
                0.0,
                float(np.sum(e_s[:-1])) / w,
                float(np.sum(etrs[:-1] * e_s[:-1])) / w,
                float(np.sum(e_s[:-1])) * 0.05 / w,
                float(ch["state"] + ch["extremum"]),
                float(ch["etr"]),
            ])
            rows.append(feats)
            times.append(t)

    X = np.asarray(rows)
    y = np.asarray(times)
    # Two-stage fit: the compute coefficients come from the dense rows alone
    # (same conditioning as the seed fit); the two per-channel θ_net's then
    # explain the residual of the partitioned rows over their compute share —
    # this keeps the two row populations from fighting over the collinear
    # compute columns.
    dense_sel = (X[:, 5] == 0.0) & (X[:, 6] == 0.0)
    theta_c = np.maximum(fit_linear(X[dense_sel, :5], y[dense_sel]), 0.0)
    resid = y[~dense_sel] - X[~dense_sel, :5] @ theta_c
    M = X[~dense_sel, 5:7]
    theta_net_pair = np.maximum(fit_linear(M, resid), 0.0)
    theta = np.concatenate([theta_c, theta_net_pair])

    coeffs = dict(
        theta0=float(theta[0]), theta_init=float(theta[1]),
        theta_v=float(theta[1]), theta_e=float(theta[2]),
        theta_etr=float(theta[3]), theta_m=float(theta[4]),
        theta_net=float(theta_net_pair[0]),
        theta_net_etr=float(theta_net_pair[1]),
    )
    pred = X @ theta
    r2 = 1 - np.sum((y - pred) ** 2) / max(np.sum((y - y.mean()) ** 2), 1e-9)
    if write:
        save_coeffs(coeffs)
    emit("fit_cost_model/r2", 0.0, f"r2={r2:.3f};n={len(y)};coeffs={coeffs}")
    return coeffs


def main():
    enable_compile_cache()
    run()


if __name__ == "__main__":
    main()
