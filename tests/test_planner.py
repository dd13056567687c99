"""Cost model: statistics accuracy, recurrences, plan discrimination."""
import numpy as np
import pytest

from repro.core import engine as E
from repro.core import query as Q
from repro.core.planner import Planner, fit_linear, load_coeffs
from repro.core.stats import GraphStats
from repro.graphdata.queries import make_workload


@pytest.fixture(scope="module")
def stats(medium_static_graph):
    return GraphStats(medium_static_graph, n_time_buckets=16)


@pytest.fixture(scope="module")
def planner(medium_static_graph, stats):
    return Planner(medium_static_graph, stats)


def test_histogram_frequency_accuracy(medium_static_graph, stats):
    """H(val, full-lifespan) should approximate exact value counts."""
    g = medium_static_graph
    b = g.meta["builder"]
    k = b.key_ids["country"]
    col = g.vprops[k]
    vals = col.vals.reshape(-1)
    vals = vals[vals >= 0]
    uniq, cnts = np.unique(vals, return_counts=True)
    for v, c in list(zip(uniq, cnts))[:8]:
        h = stats.h_lookup(k, int(v), None)
        assert h.f > 0
        # tiled estimate within 3x of exact (variance-bounded tiles)
        assert 0.33 * c <= h.f <= 3.0 * c, (v, c, h.f)


def test_degree_table(medium_static_graph, stats):
    g = medium_static_graph
    b = g.meta["builder"]
    vt, et = b.v_type_ids, b.e_type_ids
    d = stats.degree(vt["person"], et["follows"], Q.DIR_OUT)
    exact = (g.e_type == et["follows"]).sum() / g.type_counts[vt["person"]]
    assert abs(d - exact) / max(exact, 1) < 0.05


def test_estimates_monotone_in_hops(planner, medium_static_graph):
    wl = make_workload(medium_static_graph, templates=("Q4",), n_per_template=1)
    est = planner.estimate(wl[0].qry, split=wl[0].qry.n_vertices - 1)
    assert est.t_ms > 0
    assert len(est.steps) == wl[0].qry.n_vertices


def test_choose_returns_valid_split(planner, medium_static_graph):
    wl = make_workload(medium_static_graph, n_per_template=2)
    for inst in wl:
        best = planner.choose(inst.qry)
        assert 0 <= best.split < inst.qry.n_vertices
        if inst.qry.agg_op != Q.AGG_NONE:
            assert best.split == 0


def test_etr_selectivity_sampled(stats):
    for op, p in stats.etr_select.items():
        assert 0.0 <= p <= 1.0
    # before+after ≈ complement-ish on interval starts
    sb = stats.etr_select[1]   # starts-before
    sa = stats.etr_select[3]   # starts-after
    assert 0.8 <= sb + sa <= 1.05


def test_stats_size_reported(stats):
    rep = stats.size_report()
    assert rep["n_tiles"] > 0
    assert rep["bytes_tiled"] <= rep["bytes_raw"]


def test_fit_linear_recovers_coefficients():
    rng = np.random.default_rng(0)
    X = rng.normal(size=(200, 3))
    theta = np.asarray([2.0, -1.0, 0.5])
    y = X @ theta + rng.normal(scale=1e-3, size=200)
    got = fit_linear(X, y)
    np.testing.assert_allclose(got, theta, atol=1e-2)


def test_cost_model_discriminates(medium_static_graph, planner):
    """The planner's *ranking* should correlate with actual execution: the
    chosen plan should not be the worst plan (paper Sec. 6.4 criterion)."""
    import time
    wl = make_workload(medium_static_graph, templates=("Q2", "Q7"),
                       n_per_template=2, seed=4)
    for inst in wl:
        times = {}
        for split in range(inst.qry.n_vertices):
            E.count_results(medium_static_graph, inst.qry, split=split)  # warm
            t0 = time.perf_counter()
            for _ in range(3):
                E.count_results(medium_static_graph, inst.qry, split=split)
            times[split] = time.perf_counter() - t0
        chosen = planner.choose(inst.qry).split
        worst = max(times, key=times.get)
        best = min(times, key=times.get)
        # allow ties within noise: chosen must be within 2x of best
        assert times[chosen] <= max(2.0 * times[best], times[worst] * 0.999), (
            inst.template, chosen, times)


#: (split, batch t_ms) that ``choose_batch`` gave for each template's batch
#: below with the default coefficients before the hop-delivery lowering
#: lost its cost columns; the estimates and the splits did not move
BATCH_PLANS = {
    "Q1": (1, 4.125541661651531), "Q2": (1, 3.4168502371541507),
    "Q3": (1, 6.085907083333334), "Q4": (2, 12.952839802742005),
    "Q5": (2, 5.83985624749027), "Q6": (2, 7.18348),
    "Q7": (2, 10.203591509360567), "Q2-min": (0, 4.2971657996617205),
}


@pytest.mark.parametrize("template", sorted(BATCH_PLANS))
def test_batch_plan_unchanged(medium_static_graph, stats, template):
    """A batch of four instances of each served template (and the MIN
    variant of Q2) plans to the recorded split at the recorded cost, and
    every candidate's cost is its feature row dotted with θ."""
    from repro.core.planner import DEFAULT_COEFFS, coeff_vector
    from repro.graphdata.queries import to_minmax

    g = medium_static_graph
    base = template.split("-")[0]
    wl = make_workload(g, templates=(base,), n_per_template=4, seed=3)
    if template.endswith("-min"):
        wl = [to_minmax(inst, g) for inst in wl]
    planner = Planner(g, stats, coeffs=dict(DEFAULT_COEFFS))
    est = planner.choose_batch([inst.qry for inst in wl])
    split, t_ms = BATCH_PLANS[template]
    assert est.split == split
    assert est.t_ms == pytest.approx(t_ms, rel=1e-12, abs=0.0)
    theta = coeff_vector(planner.coeffs)
    assert [c["split"] for c in est.candidates] == \
        planner.enumerate_plans(wl[0].qry)
    for c in est.candidates:
        assert c["t_ms"] == pytest.approx(float(c["features"] @ theta),
                                          rel=1e-12)
