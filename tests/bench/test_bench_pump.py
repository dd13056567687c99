"""The wall-clock open-loop pump, driven through the real scheduler with
the synthetic ``FakeDispatcher`` standing in for the device."""
import os
import sys
import time

import numpy as np
import pytest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import gen, system, templates  # noqa: E402
from bench.pump import ClosedLoop, OpenLoop  # noqa: E402

GRAPH = dict(n_persons=120, dynamic=False, posts_per_person=4.0,
             comments_per_person=8.0, forums_per_person=0.8, avg_follows=10.2,
             interests_per_person=4.0, tags_per_message=1.22,
             memberships_per_person=3.0, likes_per_person=2.0, align=16,
             max_tags_per_message=4, max_interests=12)


@pytest.fixture(scope="module")
def world():
    g = gen.generate(GRAPH, 11)
    return g, system.to_graph(g)


def sched_with(tg, service_s):
    from repro.core.planner import DEFAULT_COEFFS
    from repro.serving import BatchScheduler
    from repro.serving.testing import FakeDispatcher

    def model(sched, queries, split, mode, engine, impl, pt):
        time.sleep(service_s)
        return service_s

    fake = FakeDispatcher(service_model=model)
    s = BatchScheduler(tg, engine="dense", impl="xla", dispatcher=fake,
                       coeffs=dict(DEFAULT_COEFFS))
    return s, fake


def queries(g, names):
    rng = np.random.default_rng(5)
    pool = templates.pools(g)
    return [system.to_query(templates.draw(n, rng, pool)) for n in names]


def test_latency_runs_from_the_due_time(world):
    g, tg = world
    s, _ = sched_with(tg, 0.5)
    due = [0.0, 0.25, 0.3]
    loop = OpenLoop(s, queries(g, ["Q2"] * 3), due, ["Q2"] * 3, cap=8,
                    grace_s=5.0)
    t0 = time.perf_counter()
    w = loop.run(t0)
    lat = w.done_t - (t0 + np.asarray(due))
    assert w.status == ["done"] * 3
    # q0 is served alone; q1 and q2 arrive during its dispatch and wait
    assert len(w.flushes) == 2 and w.flushes[0].queries == [0]
    assert lat[0] >= 0.5
    assert lat[1] >= 1.0 - 0.25 and lat[2] >= 1.0 - 0.3
    assert lat[1] > lat[2]                     # same completion, due earlier
    assert np.all(w.late_s >= 0)


def test_a_query_done_after_the_grace_counts_as_not_done(world):
    g, tg = world
    s, _ = sched_with(tg, 0.3)
    loop = OpenLoop(s, queries(g, ["Q2", "Q7"]), [0.0, 0.01], ["Q2", "Q7"],
                    cap=8, grace_s=0.05)
    w = loop.run(time.perf_counter())
    assert "late" in w.status
    assert np.isnan(w.done_t[w.status.index("late")])


def test_at_most_cap_per_group_per_flush(world):
    g, tg = world
    s, fake = sched_with(tg, 0.01)
    names = ["Q2"] * 5 + ["Q7"]
    loop = OpenLoop(s, queries(g, names), [0.0] * 6, names, cap=2,
                    grace_s=5.0)
    w = loop.run(time.perf_counter())
    assert w.status == ["done"] * 6
    assert max(c.n_real for c in fake.calls) <= 2
    assert sum(c.n_real for c in fake.calls) == 6
    for f in w.flushes:
        per = {}
        for i in f.queries:
            per[names[i]] = per.get(names[i], 0) + 1
        assert max(per.values()) <= 2


def test_closed_clients_send_after_their_answer(world):
    g, tg = world
    s, fake = sched_with(tg, 0.05)
    qs = queries(g, ["Q2", "Q7"] * 40)
    sent = []

    def make(i, at):
        sent.append(at)
        return qs[i], ["Q2", "Q7"][i % 2]

    loop = ClosedLoop(s, make, clients=3, think_s=0.1, seconds=0.6, cap=8,
                      grace_s=5.0)
    t0 = time.perf_counter()
    w = loop.run(t0)
    assert sent[:3] == [0.0, 0.0, 0.0]           # every client at once
    assert all(x < 0.6 for x in sent) and len(sent) >= 6
    assert w.status == ["done"] * len(sent)
    # a client's next query is due its think time after its last answer
    done = sorted(w.done_t[:3] - t0)
    assert min(w.due[3:]) >= done[0] + 0.1 - 1e-6
    assert w.late_s.size == 0
