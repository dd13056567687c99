"""The benchmark's graph generator and plain reference: fixed shapes across
seeds, agreement with the system's path-enumerating oracle on small graphs
(every template, static and bucket mode), and a bfloat16 control that the
check tells apart."""
import os
import sys

import numpy as np
import pytest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import gen, reference, system, templates  # noqa: E402

GRAPH = dict(n_persons=150, dynamic=False, posts_per_person=4.0,
             comments_per_person=8.0, forums_per_person=0.8, avg_follows=10.2,
             interests_per_person=4.0, tags_per_message=1.22,
             memberships_per_person=3.0, likes_per_person=2.0, align=16,
             max_tags_per_message=4, max_interests=12)
STATIC = ["Q1", "Q2", "Q3", "Q4", "Q5", "Q6", "Q7", "Q2-min"]
BUCKET = ["Q1", "Q2", "Q3", "Q4", "Q5", "Q6", "Q7", "Q8"]


def arrays(g):
    return [g.v_type, g.v_life, g.e_src, g.e_type, g.e_life] + [
        a for col in g.vprops.values() for a in col]


@pytest.mark.parametrize("dynamic", [False, True])
def test_every_seed_gives_the_same_shapes(dynamic):
    p = dict(GRAPH, dynamic=dynamic)
    a, b = gen.generate(p, 1), gen.generate(p, 2 ** 33 + 7)
    assert [x.shape for x in arrays(a)] == [x.shape for x in arrays(b)]
    assert not np.array_equal(a.e_dst, b.e_dst)
    assert np.array_equal(arrays(a)[3], gen.generate(p, 1).e_type)
    # referential integrity: an edge lives inside its source's lifespan
    assert np.all(a.v_life[a.e_src, 0] <= a.e_life[:, 0])
    assert np.all(a.e_life[:, 0] < a.e_life[:, 1])
    assert np.all(np.diff(a.v_type) >= 0)       # type-major


@pytest.mark.parametrize("dynamic", [False, True])
def test_reference_agrees_with_the_systems_oracle(dynamic):
    from repro.core import engine as E
    from repro.core.ref_engine import RefEngine

    g = gen.generate(dict(GRAPH, dynamic=dynamic), 21)
    oracle = RefEngine(system.to_graph(g))
    mode, names = ("bucket", BUCKET) if dynamic else ("static", STATIC)
    m = E.MODE_BUCKET if dynamic else E.MODE_STATIC
    ref = reference.Reference(g, mode, 16)
    rng = np.random.default_rng(3)
    pool = templates.pools(g)
    nonzero = 0
    for name in names:
        for _ in range(2):
            q = templates.draw(name, rng, pool)
            got = ref.answer(q)
            pq = system.to_query(q)
            if q["agg"]:
                want = oracle.aggregate(pq, mode=m)
                pv = got["per_vertex"]
                assert {int(i): got["minmax"][i]
                        for i in np.flatnonzero(pv)} == want
                assert sorted(np.flatnonzero(pv)) == sorted(want)
            else:
                want = np.asarray(oracle.count(pq, mode=m, n_buckets=16))
                assert np.array_equal(np.ravel(got["total"]), np.ravel(want))
            nonzero += bool(np.any(got["total"]))
    assert nonzero >= 4


def test_bucket_mode_refuses_off_grid_lifespans():
    g = gen.generate(dict(GRAPH, dynamic=True), 2)
    g.e_life[0, 1] = 1000                     # not on the 69-day grid
    with pytest.raises(ValueError):
        reference.Reference(g, "bucket", 16)


def test_bfloat16_control_fails_the_check():
    """Path counts above 256 lose integers in bfloat16: the check sees it."""
    g = gen.generate(dict(GRAPH, n_persons=300), 7)
    exact = reference.Reference(g, "static")
    low = reference.Reference(g, "static", precision="bfloat16")
    rng = np.random.default_rng(0)
    pool = templates.pools(g)
    qs = [templates.draw("Q4", rng, pool) for _ in range(4)]
    sp = reference.sparse
    wrong = [not reference.agrees(sp(low.answer(q)), sp(exact.answer(q)))
             for q in qs]
    assert sum(wrong) >= 3
    for q in qs:
        assert reference.agrees(sp(exact.answer(q)), sp(exact.answer(q)))


def test_a_min_answer_differs_in_any_count_or_value():
    g = gen.generate(GRAPH, 4)
    ref = reference.Reference(g, "static")
    rng = np.random.default_rng(1)
    pool = templates.pools(g)
    q = next(q for q in (templates.draw("Q2-min", rng, pool)
                         for _ in range(50)) if ref.answer(q)["total"] > 0)
    want = ref.answer(q)
    assert reference.agrees(reference.sparse(want), reference.sparse(want))
    i = int(np.flatnonzero(want["per_vertex"])[0])
    for key, j in (("per_vertex", i), ("minmax", i), ("per_vertex", 0 if i else 1)):
        bad = {k: (None if v is None else np.array(v, np.float64))
               for k, v in want.items()}
        bad[key][j] += 1
        assert not reference.agrees(reference.sparse(bad),
                                    reference.sparse(want)), (key, j)
    assert not reference.agrees(dict(reference.sparse(want), mm=None),
                                reference.sparse(want))


@pytest.mark.parametrize("workers", [1, 2])
def test_check_many_in_worker_processes(workers):
    """Spawned workers regenerate the graph from its seed and agree with
    answers made here; the bfloat16 control disagrees on large counts."""
    p, seed = dict(GRAPH, n_persons=300), 7
    g = gen.generate(p, seed)
    ref = reference.Reference(g, "static")
    rng = np.random.default_rng(0)
    pool = templates.pools(g)
    qs = [templates.draw(n, rng, pool) for n in ("Q4", "Q2-min", "Q3") * 2]
    items = [(q, reference.sparse(ref.answer(q))) for q in qs]
    items[0] = (qs[0], dict(items[0][1], total=items[0][1]["total"] + 1))
    got = reference.check_many(p, seed, "static", 16, items, workers=workers)
    assert [ok for ok, _ in got] == [False] + [True] * 5
    assert [nz for _, nz in got] == [bool(np.any(ref.answer(q)["total"]))
                                     for q in qs]
    low = reference.check_many(p, seed, "static", 16, [(q, None) for q in qs],
                               control="bfloat16", workers=workers)
    assert sum(not ok for ok, _ in low) >= 2
