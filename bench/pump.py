"""Load on the wall clock, through ``submit`` and ``flush``.

``OpenLoop``: an arrival thread releases each query at its due time
(window start plus its offset) and records how late it woke.
``ClosedLoop``: each client sends its next query ``think_s`` after its
last answer, until the window closes; a query is due when it is sent.

Both serve alike: the main loop submits what has arrived, oldest first and
at most ``cap`` per shape group per flush (the rest wait on the client
side, and the wait counts), flushes, and stamps every answer of the flush
done once ``flush`` has returned it to the host.  A query's latency is its
completion minus its due time.  The loop ends when every query has been
served, or ``grace_s`` after the last due time; a query not done by then
is not done.

``span(name)`` wraps each phase (``bench.wait_arrival``, ``bench.submit``,
``bench.flush``, ``bench.fetch``); the harness passes
``jax.profiler.TraceAnnotation`` so that the device trace can attribute its
idle gaps.  ``on_flush(k, "start" | "end")`` lets the harness start and stop
the profiler around chosen flushes.
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import threading
import time
from typing import Callable, List, Optional, Sequence

import numpy as np


@dataclasses.dataclass
class FlushRecord:
    t_start: float               # bench clock before ``submit``
    t_done: float                # answers stored on the host
    queries: List[int]           # query ids in submission order
    dispatches: list             # ``GroupDispatch`` of this flush


@dataclasses.dataclass
class WindowResult:
    due: np.ndarray              # due offset per query, from window start
    done_t: np.ndarray           # completion time per query (NaN: not done)
    status: List[str]            # "done", the scheduler's status, or "late"
    answers: list                # ``ServedResult`` per query (None: none)
    late_s: np.ndarray           # arrival-thread lateness per query
    flushes: List[FlushRecord]


def _null_span(name: str):
    return contextlib.nullcontext()


class _Server:
    """The serving half both loops share: one flush of what is pending."""

    def __init__(self, sched, groups: list, cap: int, span: Callable,
                 on_flush: Optional[Callable], clock: Callable[[], float]):
        self.sched, self.groups, self.cap = sched, groups, int(cap)
        self.span, self.on_flush, self.clock = span, on_flush, clock
        self.flushes: List[FlushRecord] = []
        self.status: List[str] = []
        self.answers: list = []
        self.done_t: List[float] = []

    def add(self, n: int) -> None:
        """Room for ``n`` more queries."""
        self.status += ["missing"] * n
        self.answers += [None] * n
        self.done_t += [np.nan] * n

    def flush(self, pending: collections.deque, queries) -> list:
        """Serve up to ``cap`` pending queries per group; returns the ids
        served, leaving the rest in ``pending`` in their order."""
        k = len(self.flushes)
        if self.on_flush is not None:
            self.on_flush(k, "start")
        t_start = self.clock()
        batch, rest, per = [], collections.deque(), {}
        for i in pending:
            g = self.groups[i]
            if per.get(g, 0) < self.cap:
                per[g] = per.get(g, 0) + 1
                batch.append(i)
            else:
                rest.append(i)
        pending.clear()
        pending.extend(rest)
        with self.span("bench.submit"):
            for i in batch:
                self.sched.submit(queries[i])
        with self.span("bench.flush"):
            out = self.sched.flush()
        with self.span("bench.fetch"):
            for i, r in zip(batch, out):
                self.answers[i] = r
                self.status[i] = r.status
            t_done = self.clock()
            for i, r in zip(batch, out):
                if r.status == "done":
                    self.done_t[i] = t_done
        self.flushes.append(FlushRecord(t_start, t_done, batch,
                                        list(self.sched.last_dispatches)))
        if self.on_flush is not None:
            self.on_flush(k, "end")
        return batch

    def result(self, due: np.ndarray, late: np.ndarray, end: float
               ) -> WindowResult:
        done_t = np.asarray(self.done_t, np.float64)
        over = done_t > end
        for i in np.flatnonzero(over):
            self.status[i] = "late"
        done_t[over] = np.nan
        return WindowResult(np.asarray(due, np.float64), done_t, self.status,
                            self.answers, late, self.flushes)


class OpenLoop:
    """Drives ``sched`` with ``queries`` due at ``t0 + due[i]``."""

    def __init__(self, sched, queries: Sequence, due: Sequence[float],
                 groups: Sequence, cap: int, grace_s: float,
                 span: Callable = _null_span,
                 on_flush: Optional[Callable] = None,
                 clock: Callable[[], float] = time.perf_counter):
        self.sched = sched
        self.queries = list(queries)
        self.due = np.asarray(due, np.float64)
        assert np.all(np.diff(self.due) >= 0), "due times must be sorted"
        self.groups = list(groups)
        self.cap = int(cap)
        self.grace_s = float(grace_s)
        self.span = span
        self.on_flush = on_flush
        self.clock = clock

    def run(self, t0: float) -> WindowResult:
        n = len(self.queries)
        clock = self.clock
        late = np.zeros(n)
        arrived: List[int] = []
        cond = threading.Condition()
        stop = threading.Event()

        def arrivals():
            for i in range(n):
                target = t0 + self.due[i]
                while not stop.is_set():
                    dt = target - clock()
                    if dt <= 0:
                        break
                    stop.wait(dt)
                if stop.is_set():
                    return
                late[i] = clock() - target
                with cond:
                    arrived.append(i)
                    cond.notify()

        th = threading.Thread(target=arrivals, name="bench-arrivals",
                              daemon=True)
        srv = _Server(self.sched, self.groups, self.cap, self.span,
                      self.on_flush, clock)
        srv.add(n)
        pending: collections.deque = collections.deque()
        end = t0 + (self.due[-1] if n else 0.0) + self.grace_s
        taken = 0
        th.start()
        try:
            while True:
                with cond:
                    pending.extend(arrived[taken:])
                    taken = len(arrived)
                if not pending:
                    if taken == n or clock() > end:
                        break
                    with self.span("bench.wait_arrival"):
                        with cond:
                            if len(arrived) == taken:
                                cond.wait(timeout=max(0.0, end - clock()))
                    continue
                if clock() > end:
                    break
                srv.flush(pending, self.queries)
        finally:
            stop.set()
            th.join()
        return srv.result(self.due, late, end)


class ClosedLoop:
    """``clients`` clients, each sending ``make(i, at)``'s query (``i`` in
    sending order, ``at`` seconds into the window) ``think_s`` after its
    last answer, until ``seconds`` into the window.  ``make`` returns the
    system's query and its shape group."""

    def __init__(self, sched, make: Callable, clients: int, think_s: float,
                 seconds: float, cap: int, grace_s: float,
                 span: Callable = _null_span,
                 on_flush: Optional[Callable] = None,
                 clock: Callable[[], float] = time.perf_counter):
        self.sched, self.make = sched, make
        self.clients, self.think_s = int(clients), float(think_s)
        self.seconds, self.cap, self.grace_s = float(seconds), cap, grace_s
        self.span, self.on_flush, self.clock = span, on_flush, clock

    def run(self, t0: float) -> WindowResult:
        clock = self.clock
        queries, groups, due = [], [], []
        srv = _Server(self.sched, groups, self.cap, self.span, self.on_flush,
                      clock)
        ready = [t0] * self.clients          # when each idle client sends
        owner = []                           # client of each query
        pending: collections.deque = collections.deque()
        close = t0 + self.seconds
        end = close + self.grace_s
        while True:
            now = clock()
            for c, t in enumerate(ready):
                if t is not None and t <= now:
                    if t >= close:
                        ready[c] = None
                        continue
                    q, grp = self.make(len(queries), t - t0)
                    queries.append(q)
                    groups.append(grp)
                    due.append(t - t0)
                    owner.append(c)
                    srv.add(1)
                    pending.append(len(queries) - 1)
                    ready[c] = None
            if not pending:
                waits = [t for t in ready if t is not None]
                if not waits or now > end:
                    break
                with self.span("bench.wait_arrival"):
                    time.sleep(max(0.0, min(waits) - clock()))
                continue
            if now > end:
                break
            for i in srv.flush(pending, queries):
                ready[owner[i]] = srv.done_t[i] + self.think_s \
                    if srv.status[i] == "done" else clock() + self.think_s
        return srv.result(np.asarray(due), np.zeros(0), end)
