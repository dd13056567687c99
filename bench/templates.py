"""The LDBC-derived Q1-Q8 templates as plain data.

The same shapes as the system's ``graphdata/queries.py`` (hops, vertex and
edge types, directions, property and time clauses, ETR operators) and the
same parameter draws: values over the frequent-value pools, time windows
``[lo, T)`` with ``lo`` on the 16-bucket grid in the first half of the
horizon.  ``Params`` makes the draws: values uniform over a pool (the
system's draw), or Zipf-ranked by frequency with a hot set that drifts
over the window, as a traffic file's ``params`` says.  A query is a dict of names and numbers, so that the
reference reads it without the system's query classes:

    {"template": "Q2", "v": [{"type": "person", "clauses": [...]}, ...],
     "e": [{"type": "created", "dir": "out", "etr": None}, ...],
     "agg": None | "min", "agg_key": None | "length"}

A clause is ``{"kind": "prop", "key", "cmp": "==" | "!=" | "in", "value",
"conj": "and" | "or"}`` or ``{"kind": "time", "cmp", "interval": [lo, hi],
"conj"}`` with an interval comparator ("<<", "<", ">>", ">", "during",
"==", "in", "overlaps"); an ETR comparator is one of the first four or
"overlaps".
"""
from __future__ import annotations

from typing import Callable, Dict

import numpy as np

from .gen import GENDERS, LANGS, T_HORIZON, RawGraph, freq_values


def prop(key: str, cmp: str, value: int, conj: str = "and") -> dict:
    return dict(kind="prop", key=key, cmp=cmp, value=int(value), conj=conj)


def time(cmp: str, interval, conj: str = "and") -> dict:
    return dict(kind="time", cmp=cmp, interval=[int(interval[0]),
                                                int(interval[1])], conj=conj)


def v(vtype: str, *clauses) -> dict:
    return dict(type=vtype, clauses=list(clauses))


def e(etype: str, direction: str, etr=None) -> dict:
    return dict(type=etype, dir=direction, etr=etr)


def query(template: str, vs, es, agg=None, agg_key=None) -> dict:
    assert len(vs) == len(es) + 1 and es[0]["etr"] is None
    return dict(template=template, v=list(vs), e=list(es), agg=agg,
                agg_key=agg_key)


class Params:
    """The parameter draws of one query due ``at`` seconds into the window.

    ``dist`` is a traffic file's ``params``: ``{"dist": "uniform"}``, or
    ``{"dist": "zipf", "s": <exponent>, "drift_s": <seconds>}``: the
    ``k``-th most frequent pool value is drawn with weight ``1 / k**s``,
    and the ranking rotates by one place every ``drift_s`` seconds (0: no
    drift)."""

    def __init__(self, rng: np.random.Generator, pools: dict,
                 dist: dict = None, at: float = 0.0):
        self.rng, self.pools, self.at = rng, pools, float(at)
        self.dist = dist or {"dist": "uniform"}
        if self.dist["dist"] not in ("uniform", "zipf"):
            raise ValueError(f"no parameter distribution {self.dist!r}")

    def value(self, key: str) -> int:
        pool = self.pools[key]
        if self.dist["dist"] == "uniform":
            return int(self.rng.choice(pool))
        w = 1.0 / np.arange(1, pool.size + 1) ** float(self.dist["s"])
        rank = int(self.rng.choice(pool.size, p=w / w.sum()))
        drift = float(self.dist.get("drift_s", 0))
        shift = int(self.at // drift) if drift > 0 else 0
        return int(pool[(rank + shift) % pool.size])

    def interval(self, align: int = 16):
        step = -(-T_HORIZON // align)
        lo = int(self.rng.integers(0, T_HORIZON // 2) // step * step)
        return (lo, T_HORIZON)


F = GENDERS.index("f")
M = GENDERS.index("m")
EN = LANGS.index("en")


def _q1(P):
    tagx, tagy = P.value("tag"), P.value("tag")
    cty = P.value("country")
    return query("Q1", (
        v("post", prop("tag", "in", tagx)),
        v("forum", time("overlaps", P.interval())),
        v("post", prop("tag", "in", tagy)),
        v("person", prop("country", "==", cty))), (
        e("containerOf", "in"),
        e("containerOf", "out", etr="<"),
        e("hasMember", "in")))


def _q2(P):
    tag, cty = P.value("tag"), P.value("country")
    return query("Q2", (
        v("person", prop("country", "==", cty), prop("gender", "==", F, "or")),
        v("post", prop("tag", "in", tag), time(">", P.interval())),
        v("person", prop("hasInterest", "in", tag))), (
        e("created", "out"),
        e("likes", "in")))


def _q3(P):
    c1, c2 = P.value("country"), P.value("country")
    return query("Q3", (
        v("person", prop("country", "==", c1)),
        v("post", time("overlaps", P.interval())),
        v("person", prop("country", "==", c2)),
        v("person")), (
        e("likes", "out"),
        e("likes", "in", etr="<<"),
        e("follows", "out")))


def _q4(P):
    c1 = P.value("country")
    i1, i2 = P.interval(), P.interval()
    return query("Q4", (
        v("person", prop("country", "==", c1)),
        v("person", time("overlaps", i1)),
        v("person"),
        v("person", time("overlaps", i2)),
        v("person")), (
        e("follows", "out"),
        e("follows", "out", etr="<"),
        e("follows", "out", etr="<"),
        e("follows", "out")))


def _q5(P):
    tagx, tagy = P.value("tag"), P.value("tag")
    cty = P.value("country")
    i1, i2, i3 = P.interval(), P.interval(), P.interval()
    return query("Q5", (
        v("person", prop("country", "==", cty)),
        v("post", prop("tag", "in", tagx), time("overlaps", i1)),
        v("forum", time("overlaps", i2)),
        v("post", prop("tag", "in", tagy), time(">", i3)),
        v("person", prop("gender", "==", M))), (
        e("created", "out"),
        e("containerOf", "in"),
        e("containerOf", "out", etr=">>"),
        e("created", "in")))


def _q6(P):
    tag = P.value("tag")
    return query("Q6", (
        v("person", prop("gender", "==", F)),
        v("comment"),
        v("post", prop("tag", "in", tag), time("overlaps", P.interval())),
        v("comment"),
        v("person")), (
        e("created", "out"),
        e("replyOf", "out"),
        e("replyOf", "in", etr=">>"),
        e("created", "in")))


def _q7(P):
    c1, c2 = P.value("country"), P.value("country")
    i1, i2 = P.interval(), P.interval()
    return query("Q7", (
        v("post", prop("language", "==", EN), time("overlaps", i1)),
        v("person", prop("country", "==", c1)),
        v("person", prop("country", "==", c2), time("overlaps", i2)),
        v("post")), (
        e("created", "in"),
        e("follows", "out", etr=">"),
        e("created", "out", etr="<")))


def _q8(P):
    w1, w2 = P.value("worksAt"), P.value("worksAt")
    return query("Q8", (
        v("person", prop("worksAt", "==", w1)),
        v("person", time("overlaps", P.interval())),
        v("person", prop("worksAt", "==", w2))), (
        e("follows", "out"),
        e("follows", "in", etr="overlaps")))


def _min_of(build: Callable) -> Callable:
    """The MIN variant: the post ``length`` at the last vertex, grouped by
    the first vertex (the system's ``to_minmax``)."""
    def b(P):
        q = build(P)
        q.update(template=q["template"] + "-min", agg="min", agg_key="length")
        return q
    return b


BUILDERS: Dict[str, Callable] = {
    "Q1": _q1, "Q2": _q2, "Q3": _q3, "Q4": _q4, "Q5": _q5, "Q6": _q6,
    "Q7": _q7, "Q8": _q8, "Q2-min": _min_of(_q2),
}


def pools(g: RawGraph) -> dict:
    return {k: freq_values(g, k) for k in ("tag", "country", "worksAt")}


def draw(name: str, rng: np.random.Generator, pool: dict,
         dist: dict = None, at: float = 0.0) -> dict:
    """One instance of template ``name`` with parameters from ``rng``,
    drawn as ``dist`` says (``Params``) for a query due at ``at``."""
    return BUILDERS[name](Params(rng, pool, dist, at))
