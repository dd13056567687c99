"""The one traffic generator: a cell's queries from its traffic file.

A traffic mix is data, ``bench/traffic/<mix>.json``:

    {"loop": {"kind": "open", "arrivals": "poisson", "rate_qps": 16.0},
     "templates": {"Q1": 1, "Q2": 1, "Q4": 2},
     "params": {"dist": "uniform"},
     "drain_grace_s": 60, "warm_seed": 20240101,
     "trace_after_s": 10, "trace_min_s": 4}

``loop`` is one of

* ``{"kind": "open", "arrivals": "poisson", "rate_qps": r}``:
  ``round(r * seconds)`` queries due at sorted uniform times over the
  window (a Poisson process given its count);
* ``{"kind": "open", "arrivals": "onoff", "rate_qps": r, "burst_qps": b,
  "on_s": a, "period_s": p}``: bursts at rate ``b`` for the first ``a``
  seconds of every ``p``, rate ``r`` between them; each stretch holds its
  rate times its length in queries, due at uniform times inside it;
* ``{"kind": "closed", "clients": c, "think_s": t}``: ``c`` clients, each
  sending its next query ``t`` seconds after its last answer.

``templates`` weighs the templates (each must be one the configuration
serves): an open window gives each its share of the queries by largest
remainder, in an order drawn from the seed; a closed loop sends them in
blocks that hold each share once, each block in its own order.  So every
seed offers the same work in another order.  ``params`` is the parameter
distribution of ``templates.Params``.
"""
from __future__ import annotations

from typing import Dict, List, Tuple

import numpy as np

from . import templates
from .gen import RawGraph

LOOPS = ("open", "closed")


def weights(traffic: dict) -> Dict[str, float]:
    """The templates the traffic sends, with their positive weights."""
    w = {k: float(v) for k, v in traffic["templates"].items() if v > 0}
    if not w:
        raise ValueError("the traffic sends no template")
    return w


def shares(w: Dict[str, float], n: int) -> Dict[str, int]:
    """``n`` queries split by weight, the remainder by largest remainder
    (ties to the earlier template)."""
    names = list(w)
    total = sum(w.values())
    exact = np.array([w[k] * n / total for k in names])
    base = np.floor(exact).astype(int)
    rest = n - int(base.sum())
    order = np.argsort(-(exact - base), kind="stable")
    base[order[:rest]] += 1
    return dict(zip(names, base.tolist()))


def open_due(loop: dict, seconds: float, rng) -> np.ndarray:
    """Sorted due offsets in ``[0, seconds)`` of an open loop."""
    if loop["arrivals"] == "poisson":
        n = max(1, int(round(loop["rate_qps"] * seconds)))
        return np.sort(rng.uniform(0.0, seconds, size=n))
    if loop["arrivals"] == "onoff":
        period, on = float(loop["period_s"]), float(loop["on_s"])
        out = []
        for start in np.arange(0.0, seconds, period):
            for lo, hi, rate in ((start, start + on, loop["burst_qps"]),
                                 (start + on, start + period,
                                  loop["rate_qps"])):
                hi = min(hi, seconds)
                if hi > lo:
                    k = int(round(rate * (hi - lo)))
                    out.append(rng.uniform(lo, hi, size=k))
        due = np.sort(np.concatenate(out)) if out else np.zeros(0)
        if due.size == 0:
            raise ValueError("an on/off schedule with no query")
        return due
    raise ValueError(f"no arrival process {loop['arrivals']!r}")


def open_window(traffic: dict, g: RawGraph, seed: int, seconds: float
                ) -> Tuple[List[dict], np.ndarray]:
    """(plain queries, sorted due offsets) of an open-loop window."""
    rng = np.random.default_rng([seed, 1])
    due = open_due(traffic["loop"], seconds, rng)
    n = due.size
    cnt = shares(weights(traffic), n)
    names = np.array(list(cnt))
    order = rng.permutation(np.repeat(np.arange(len(names)),
                                      list(cnt.values())))
    pool = templates.pools(g)
    dist = traffic.get("params")
    qs = [templates.draw(str(names[t]), rng, pool, dist, at)
          for t, at in zip(order, due)]
    return qs, due


class ClosedStream:
    """The queries of a closed loop, drawn in the order they are sent:
    ``next(at)`` is the next query, sent ``at`` seconds into the window."""

    def __init__(self, traffic: dict, g: RawGraph, seed: int):
        self.rng = np.random.default_rng([seed, 1])
        w = weights(traffic)
        scale = 1.0 / min(w.values())
        self.block = np.repeat(np.array(list(w)),
                               [int(round(v * scale)) for v in w.values()])
        self.pool = templates.pools(g)
        self.dist = traffic.get("params")
        self.queue: List[str] = []

    def next(self, at: float) -> dict:
        if not self.queue:
            self.queue = list(self.rng.permutation(self.block))
        return templates.draw(str(self.queue.pop()), self.rng, self.pool,
                              self.dist, at)
