"""Plain reference for temporal path queries: a backward count over paths.

Independent of the system under test: it reads the benchmark's own graph
(``gen.RawGraph``) and plain queries (``templates``), and imports nothing of
the system.  The semantics are those of the system's oracle
(``core/ref_engine.py``), computed by dynamic programming instead of path
enumeration so that it answers at 100k persons in well under a second:

* static mode: the number of paths whose every vertex and edge matches its
  predicate and whose adjacent edges satisfy each ETR comparator;
* bucket mode: per time bucket, the number of such paths whose entities are
  all valid in that bucket (validity = lifespan, narrowed by the values
  that an equality or membership clause matched).  Every lifespan endpoint
  must lie on the bucket grid or at the horizon, which makes "the running
  intersection of validities overlaps the bucket" the same as "every
  entity overlaps it"; the reference checks this and refuses otherwise;
* MIN: per first vertex, the number of paths and the least ``agg_key`` of
  their last vertex (slot 0).

The state runs from the last vertex back to the first.  ``S_h[v]`` is the
number of path suffixes from vertex position ``h`` starting at ``v`` (its
predicate included); a hop is a sum over the matching edges of a type.  An
ETR comparator on hop ``h + 1`` ties the sum to the edge taken at hop
``h``, so the state is then kept per edge and summed, for each edge of hop
``h``, over the edges of hop ``h + 1`` that the comparator admits.

``precision="bfloat16"`` rounds the state to bfloat16 after every hop: the
control a lower-precision path must be told apart from.

``check_many`` compares answers with the reference's in worker processes,
each of which generates the graph anew from its parameters and seed: they
share nothing with the system under test.
"""
from __future__ import annotations

import os
from concurrent.futures import ProcessPoolExecutor
from multiprocessing import get_context
from typing import List, Optional, Tuple

import numpy as np

from . import gen
from .gen import ETYPES, VTYPES, RawGraph

def bucket_edges(lo: int, hi: int, n: int) -> np.ndarray:
    """``n + 1`` boundaries of equal ceil-width buckets over ``[lo, hi)``."""
    width = max(1, -(-(hi - lo) // n))
    return lo + width * np.arange(n + 1, dtype=np.int64)


def compare(op: str, a_lo, a_hi, b_lo, b_hi):
    """Allen comparator ``a OP b`` over half-open intervals; empty never
    compares true."""
    if op == "<<":
        r = a_hi <= b_lo
    elif op == "<":
        r = a_lo < b_lo
    elif op == ">>":
        r = a_lo >= b_hi
    elif op == ">":
        r = a_lo > b_lo
    elif op == "during":
        r = (a_lo > b_lo) & (a_hi < b_hi)
    elif op == "==":
        r = (a_lo == b_lo) & (a_hi == b_hi)
    elif op == "in":
        r = (a_lo >= b_lo) & (a_hi <= b_hi)
    elif op == "overlaps":
        r = (a_lo < b_hi) & (b_lo < a_hi)
    else:
        raise ValueError(op)
    return r & (a_lo < a_hi) & (b_lo < b_hi)


class Reference:
    """Answers plain queries over one graph, in ``mode`` "static" or
    "bucket" with ``n_buckets`` buckets."""

    def __init__(self, g: RawGraph, mode: str = "static", n_buckets: int = 16,
                 precision: str = "float64"):
        if mode not in ("static", "bucket") or precision not in (
                "float64", "bfloat16"):
            raise ValueError(f"mode {mode!r}, precision {precision!r}")
        self.g = g
        self.mode = mode
        self.B = n_buckets
        self.precision = precision
        self.edges = bucket_edges(g.lifespan[0], g.lifespan[1], n_buckets)
        self._cache = {}
        if mode == "bucket":
            grid = set(self.edges.tolist()) | {g.lifespan[1]}
            ends = [g.v_life, g.e_life] + [life for _, life in g.vprops.values()]
            for a in ends:
                used = np.unique(a)
                off = [int(t) for t in used if int(t) not in grid and t != 0]
                if off:
                    raise ValueError(f"lifespan endpoints off the bucket grid "
                                     f"(e.g. {off[:3]}): bucket counts would "
                                     f"need interval lists")

    # ---------------------------------------------------------- helpers
    def _round(self, x: np.ndarray) -> np.ndarray:
        if self.precision == "float64":
            return x
        import ml_dtypes
        return x.astype(ml_dtypes.bfloat16).astype(np.float64)

    def _bits(self, lo, hi) -> Optional[np.ndarray]:
        """Bucket-overlap masks (one bit per bucket) of ``[lo, hi)``."""
        if self.mode != "bucket":
            return None
        e = self.edges
        out = np.zeros(np.shape(lo), np.int64)
        for b in range(self.B):         # one bucket at a time: no [..., B]
            out |= ((lo < e[b + 1]) & (e[b] < hi)).astype(np.int64) << b
        return out

    def _memo(self, key, fn):
        """Per-graph tables (lifespan masks, typed edge lists), made once."""
        if key not in self._cache:
            self._cache[key] = fn()
        return self._cache[key]

    def _life_bits(self, name: str, life: np.ndarray):
        return self._memo(("bits", name), lambda: self._bits(
            life[..., 0].astype(np.int64), life[..., 1].astype(np.int64)))

    def _unpack(self, bits: np.ndarray) -> np.ndarray:
        """Bucket masks as 0/1 weights [..., B]."""
        table = self._memo("unpack", lambda: (
            (np.arange(1 << self.B)[:, None] >> np.arange(self.B)) & 1
        ).astype(np.float64))
        return table[bits]

    def _predicate(self, spec: dict):
        """(match bool[V], validity bits or None) of a vertex predicate."""
        g = self.g
        lo, hi = g.v_life[:, 0], g.v_life[:, 1]
        match = lo < hi
        if spec["type"] is not None:
            match &= g.v_type == VTYPES.index(spec["type"])
        base = self._life_bits("vertex", g.v_life)
        acc_m = acc_v = None
        for c in spec["clauses"]:
            if c["kind"] == "time":
                m = compare(c["cmp"], lo, hi, *c["interval"])
                v = base
            else:
                vals, plife = g.vprops[c["key"]]
                x = c["value"]
                if c["cmp"] == "!=":
                    has = (vals >= 0).any(1)
                    m = has & ((vals != x) | (vals < 0)).all(1)
                    v = base
                else:                               # "==" and "in"
                    hit = vals == x
                    m = hit.any(1)
                    v = None
                    if base is not None:
                        sb = self._life_bits(c["key"], plife)
                        v = np.bitwise_or.reduce(np.where(hit, sb, 0), axis=1)
            if acc_m is None:
                acc_m, acc_v = m, v
            elif c["conj"] == "and":
                acc_m = acc_m & m
                acc_v = None if v is None else acc_v & v
            else:
                if v is not None:
                    acc_v = np.where(acc_m & ~m, acc_v,
                                     np.where(m & ~acc_m, v, acc_v | v))
                acc_m = acc_m | m
        if acc_m is not None:
            match &= acc_m
            if base is not None:
                base = base & acc_v
        return match, base

    def _weight(self, match, bits) -> np.ndarray:
        """A predicate as a multiplicative weight: [n] or [n, B]."""
        if self.mode == "static":
            return match.astype(np.float64)
        return self._unpack(np.where(match, bits, 0))

    def _traversals(self, spec: dict):
        """(edge id, tail, head) of the edges a hop may take, oriented."""
        return self._memo(("hop", spec["type"], spec["dir"]),
                          lambda: self._orient(spec))

    def _orient(self, spec: dict):
        g = self.g
        sel = (g.e_type == ETYPES.index(spec["type"])) & (
            g.e_life[:, 0] < g.e_life[:, 1])
        eid = np.flatnonzero(sel)
        s, d = g.e_src[eid].astype(np.int64), g.e_dst[eid].astype(np.int64)
        if spec["dir"] == "out":
            return eid, s, d
        if spec["dir"] == "in":
            return eid, d, s
        return (np.concatenate([eid, eid]), np.concatenate([s, d]),
                np.concatenate([d, s]))

    def _segment_sum(self, seg, w, n) -> np.ndarray:
        if w.ndim == 1:
            return np.bincount(seg, weights=w, minlength=n)
        k = w.shape[1]
        idx = (seg[:, None] * k + np.arange(k)).ravel()
        return np.bincount(idx, weights=w.ravel(), minlength=n * k).reshape(
            n, k)

    def _pair_join(self, op, eid, head, eid2, tail2, cnt2):
        """For each hop-h edge t: the sum of the hop-h+1 states over the
        edges t' leaving head(t) with ``life(t) OP life(t')``.  Every
        comparator is a threshold on one endpoint of t' set by one endpoint
        of t ("overlaps" is all but the two disjoint orders), so the states
        are summed by prefix sums over t' sorted by (tail, endpoint)."""
        lo1, hi1 = self.g.e_life[eid, 0], self.g.e_life[eid, 1]
        lo2, hi2 = self.g.e_life[eid2, 0], self.g.e_life[eid2, 1]

        def count(key2, thr, cmp):
            order = np.lexsort((key2, tail2))
            span = int(max(key2.max(initial=0), thr.max(initial=0))) + 2
            enc = tail2[order] * span + key2[order]
            csum = np.concatenate([np.zeros((1,) + cnt2.shape[1:]),
                                   np.cumsum(cnt2[order], axis=0)])
            side = "right" if cmp in (">", "<=") else "left"
            need = head * span + thr
            qo = np.argsort(need, kind="stable")    # sorted lookups run fast
            first, last, at = (np.empty(head.size, np.int64) for _ in "fla")
            first[qo] = np.searchsorted(enc, (head * span)[qo], "left")
            last[qo] = np.searchsorted(enc, (head * span + span - 1)[qo],
                                       "right")
            at[qo] = np.searchsorted(enc, need[qo], side)
            if cmp in (">", ">="):
                return csum[last] - csum[at]
            return csum[at] - csum[first]

        if op == "<<":
            return count(lo2, hi1, ">=")
        if op == "<":
            return count(lo2, lo1, ">")
        if op == ">>":
            return count(hi2, lo1, "<=")
        if op == ">":
            return count(lo2, lo1, "<")
        if op == "overlaps":
            every = count(lo2, np.zeros_like(lo1), ">=")
            return every - count(hi2, lo1, "<=") - count(lo2, hi1, ">=")
        raise ValueError(f"no ETR comparator {op!r}")

    # ------------------------------------------------------------ answer
    def answer(self, q: dict) -> dict:
        """``{"total": float | [B], "per_vertex": [V] | None,
        "minmax": [V] | None}`` for a plain query."""
        g = self.g
        V = g.n_vertices
        n = len(q["v"])
        want_min = q["agg"] == "min"
        if want_min and self.mode != "static":
            raise ValueError("MIN answers are defined in static mode")
        preds = [self._predicate(v) for v in q["v"]]
        w = [self._weight(*p) for p in preds]

        # S_{n-1}: the last vertex's predicate (and its value, for MIN)
        S = w[n - 1]
        S_min = None
        if want_min:
            vals = g.vprops[q["agg_key"]][0][:, 0].astype(np.float64)
            S_min = self._round(np.where(preds[n - 1][0], vals, np.inf))
        P = None                      # per-edge state of hop h + 1
        for h in range(n - 2, -1, -1):
            ep = q["e"][h]
            eid, tail, head = self._traversals(ep)
            em = (1.0 if self.mode == "static" else
                  self._unpack(self._life_bits("edge", g.e_life)[eid]))
            nxt = q["e"][h + 1]["etr"] if h + 1 <= n - 2 else None
            if nxt is None:
                cnt = em * S[head]
                mn = None if S_min is None else S_min[head]
            else:
                if want_min:
                    raise ValueError("MIN across an ETR hop is not defined")
                eid2, tail2, cnt2, _ = P
                gate = em * w[h + 1][head]
                cnt = np.zeros((eid.size,) + cnt2.shape[1:])
                t = np.flatnonzero(gate if gate.ndim == 1 else gate.any(1))
                u = np.flatnonzero(cnt2 if cnt2.ndim == 1 else cnt2.any(1))
                cnt[t] = gate[t] * self._pair_join(
                    nxt, eid[t], head[t], eid2[u], tail2[u], cnt2[u])
                mn = None
            cnt = self._round(cnt)
            P = (eid, tail, cnt, mn)
            S = self._round(w[h] * self._segment_sum(tail, cnt, V))
            if S_min is not None:
                alive = cnt > 0
                S_min = np.full(V, np.inf)
                np.minimum.at(S_min, tail[alive], mn[alive])
                S_min = np.where(S > 0, S_min, np.inf)
        total = self._round(S.sum(0))
        if not want_min:
            return dict(total=total, per_vertex=None, minmax=None)
        return dict(total=total, per_vertex=S, minmax=S_min)


# ------------------------------------------------------------- comparison
def sparse(ans: dict) -> dict:
    """An answer with its per-vertex arrays kept at their non-zero counts
    (small to hand between processes, and compared the same)."""
    total = np.ravel(np.asarray(ans["total"], np.float64))
    if ans.get("per_vertex") is None:
        return dict(total=total, idx=None)
    pv = np.ravel(np.asarray(ans["per_vertex"], np.float64))
    idx = np.flatnonzero(pv)
    mm = ans.get("minmax")
    return dict(total=total, idx=idx, pv=pv[idx],
                mm=None if mm is None else np.ravel(
                    np.asarray(mm, np.float64))[idx])


def agrees(served: dict, want: dict) -> bool:
    """Exact equality of every number the query answers (``sparse``
    forms): the total or per-bucket series, and for MIN the count and the
    least value of every first vertex with a path."""
    if not np.array_equal(served["total"], want["total"]):
        return False
    if want["idx"] is None:
        return True
    if served["idx"] is None or served["mm"] is None:
        return False
    return (np.array_equal(served["idx"], want["idx"])
            and np.array_equal(served["pv"], want["pv"])
            and np.array_equal(served["mm"], want["mm"]))


class Checker:
    """The reference over the graph of ``(graph, seed)``, generated here,
    and the control's reference at precision ``control`` (or none)."""

    def __init__(self, graph: dict, seed: int, mode: str, n_buckets: int,
                 control: Optional[str]):
        g = gen.generate(graph, seed)
        self.ref = Reference(g, mode, n_buckets)
        self.low = (None if control is None else
                    Reference(g, mode, n_buckets, precision=control))

    def check(self, items: list) -> List[Tuple[bool, bool]]:
        out = []
        for q, served in items:
            want = sparse(self.ref.answer(q))
            if served is None:
                served = sparse(self.low.answer(q))
            out.append((agrees(served, want),
                        bool(np.any(want["total"] != 0))))
        return out


_checker: Optional[Checker] = None       # a worker process's own


def _init_worker(*args) -> None:
    global _checker
    _checker = Checker(*args)


def _check_in_worker(items: list) -> List[Tuple[bool, bool]]:
    return _checker.check(items)


#: host memory set aside per worker: a worker's graph and reference tables
#: take 0.4-0.6 GB at the benchmark's sizes, twice that with a control
WORKER_BYTES = 4 << 30


def available_bytes() -> Optional[int]:
    """Memory this process may still take: the machine's ``MemAvailable``,
    or less where a cgroup limit leaves less (None: not known)."""
    got = []
    try:
        with open("/proc/meminfo") as f:
            for line in f:
                if line.startswith("MemAvailable:"):
                    got.append(int(line.split()[1]) * 1024)
    except OSError:
        pass
    try:
        with open("/sys/fs/cgroup/memory.max") as f:
            limit = f.read().strip()
        with open("/sys/fs/cgroup/memory.current") as f:
            used = int(f.read())
        if limit != "max":
            got.append(int(limit) - used)
    except (OSError, ValueError):
        pass
    return min(got) if got else None


def default_workers() -> int:
    """Up to 8 workers, two cores left to the rest, and no more than the
    memory available holds at ``WORKER_BYTES`` each."""
    n = min(8, len(os.sched_getaffinity(0)) - 2)
    avail = available_bytes()
    if avail is not None:
        n = min(n, avail // WORKER_BYTES)
    return max(1, n)


def check_many(graph: dict, seed: int, mode: str, n_buckets: int,
               items: list, control: Optional[str] = None,
               workers: int = 1) -> List[Tuple[bool, bool]]:
    """For each ``(plain query, sparse served answer)``: (agrees with the
    reference, the reference's answer is non-zero).  A served answer of
    None is the control's: the reference at precision ``control``.
    ``workers`` spawned processes share the items."""
    ctx = (graph, seed, mode, n_buckets, control)
    if workers <= 1 or len(items) < 2 * workers:
        return Checker(*ctx).check(items)
    k = min(len(items), 4 * workers)
    chunks = [items[i::k] for i in range(k)]
    with ProcessPoolExecutor(workers, mp_context=get_context("spawn"),
                             initializer=_init_worker, initargs=ctx) as ex:
        res = list(ex.map(_check_in_worker, chunks))
    out: list = [None] * len(items)
    for i, r in enumerate(res):
        out[i::k] = r
    return out
