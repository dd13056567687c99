"""The system under test, seen from the benchmark: its graph and query
types, built from the benchmark's own data.  The only module here (with
``harness``) that imports the system (``src/repro``)."""
from __future__ import annotations

import numpy as np

from .gen import ETYPES, KEYS, VTYPES, RawGraph


def to_graph(g: RawGraph):
    """A ``TemporalGraph`` holding copies of the benchmark's arrays."""
    from repro.core.graph import PropColumn, TemporalGraph

    vprops = {KEYS.index(k): PropColumn(vals.copy(), life.copy())
              for k, (vals, life) in g.vprops.items()}
    return TemporalGraph(
        g.v_type.copy(), g.v_life.copy(), g.e_src.copy(), g.e_dst.copy(),
        g.e_type.copy(), g.e_life.copy(), vprops, {},
        n_vertex_types=len(VTYPES), n_edge_types=len(ETYPES),
        lifespan=g.lifespan, meta=dict(params=dict(dynamic=g.dynamic)))


def to_query(q: dict):
    """The system's ``PathQuery`` for a plain query (``templates``)."""
    from repro.core import intervals as iv
    from repro.core import query as Q

    def clause(c):
        conj = Q.AND if c["conj"] == "and" else Q.OR
        if c["kind"] == "time":
            return Q.time_clause(c["cmp"], tuple(c["interval"]), conj=conj)
        return Q.prop_clause(KEYS.index(c["key"]), c["cmp"], c["value"],
                             conj=conj)

    dirs = {"out": Q.DIR_OUT, "in": Q.DIR_IN, "both": Q.DIR_BOTH}
    vp = tuple(Q.VertexPredicate(
        -1 if v["type"] is None else VTYPES.index(v["type"]),
        tuple(clause(c) for c in v["clauses"])) for v in q["v"])
    ep = tuple(Q.EdgePredicate(
        ETYPES.index(e["type"]), dirs[e["dir"]],
        etr_op=-1 if e["etr"] is None else iv.TIME_CMP_NAMES[e["etr"]])
        for e in q["e"])
    agg = {None: Q.AGG_NONE, "min": Q.AGG_MIN}[q["agg"]]
    key = -1 if q["agg_key"] is None else KEYS.index(q["agg_key"])
    return Q.PathQuery(vp, ep, agg_op=agg, agg_key=key)


def served_answer(r) -> dict:
    """A ``ServedResult``'s answer, copied to the host, in the reference's
    form."""
    total = np.asarray(r.total, np.float64)
    pv = None if r.per_vertex is None else np.asarray(r.per_vertex,
                                                      np.float64)
    mm = None if r.minmax is None else np.asarray(r.minmax, np.float64)
    return dict(total=total, per_vertex=pv, minmax=mm)
