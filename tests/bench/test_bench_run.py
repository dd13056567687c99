"""Whole runs of the harness on the CPU at a tiny size: a sound run is
correct, and each fault a cell can have, planted in the timed path after
warm-up, makes ``correct`` false.  Plus the entry point's refusals."""
import dataclasses
import json
import os
import shutil
import subprocess
import sys
import time

import numpy as np
import pytest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import gen, harness, load  # noqa: E402

#: a tiny graph: 150 persons at small per-person ratios
TINY = dict(n_persons=150, posts_per_person=4.0, comments_per_person=8.0,
            forums_per_person=0.8, likes_per_person=2.0,
            memberships_per_person=3.0, interests_per_person=4.0,
            max_interests=12)


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    """A checkout-like root whose one cell is a tiny static deployment."""
    root = tmp_path_factory.mktemp("bench_root")
    os.makedirs(root / "bench" / "configs")
    os.makedirs(root / "bench" / "traffic")
    cfg = harness.load_json(ROOT, "bench", "configs", "snb-fs-p1500.json")
    cfg.update(name="tiny", max_batch_per_group=2)
    cfg["graph"].update(TINY)
    traffic = dict(loop=dict(kind="open", arrivals="poisson", rate_qps=150),
                   templates={"Q2": 1, "Q4": 1, "Q7": 1, "Q2-min": 1},
                   params=dict(dist="uniform"), nonzero_percent_min=25,
                   drain_grace_s=30, warm_seed=1, trace_after_s=0,
                   trace_min_s=0)
    man = harness.manifest()
    man["configs"] = [dict(name="tiny", source="test", reduced=[],
                           file="bench/configs/tiny.json")]
    man["workloads"] = [dict(name="tiny.open", config="tiny",
                             traffic="t", chips=1, why="test"),
                        dict(name="tiny.closed", config="tiny",
                             traffic="c", chips=1, why="test")]
    for m in man["per_layer"] + man["end_to_end"]:
        m["workloads"] = ["tiny.open", "tiny.closed"]
    closed = dict(traffic, loop=dict(kind="closed", clients=6, think_s=0.0))
    for path, body in (("BENCHMARK.json", man),
                       ("bench/configs/tiny.json", cfg),
                       ("bench/traffic/t.json", traffic),
                       ("bench/traffic/c.json", closed)):
        with open(root / path, "w") as f:
            json.dump(body, f)
    return str(root)


def _alter(res, n_real):
    return dataclasses.replace(res, total=res.total.at[0].add(1))


def _half_batch(res, n_real):
    """The second half of the real rows answered with row 0's answer."""
    half = n_real // 2
    idx = np.arange(res.total.shape[0])
    src = np.where((idx >= n_real - half) & (idx < n_real), 0, idx)
    pick = lambda x: None if x is None else x[src]  # noqa: E731
    return dataclasses.replace(res, total=pick(res.total),
                               per_vertex=pick(res.per_vertex),
                               minmax=pick(res.minmax))


def plant(fault):
    """``after_warmup`` hook: wrap the scheduler's device dispatch."""
    def hook(sched):
        orig = sched._dispatch_jax
        last = {}
        hook.batches = []

        def dispatch(queries, split, mode, engine, impl, bucket, pt, warm):
            res, dt, cached = orig(queries, split, mode, engine, impl,
                                   bucket, pt, warm)
            hook.batches.append(pt.n_real)
            if fault == "stale":        # the step hands back its old state
                key = (bucket, pt.params.shape[0])
                res, last[key] = last.get(key, res), res
            elif fault is not None:
                res = fault(res, pt.n_real)
            return res, dt, cached

        sched._dispatch_jax = dispatch
    return hook


def run(root, fault=None):
    hook = plant(fault)
    out = harness.run_cell("tiny.open", 2 ** 32 + 5, 0.25, False,
                           time.perf_counter(), root=root, require_tpu=False,
                           compile_cache=False, workers=1, after_warmup=hook)
    return out, hook.batches


def test_sound_run_is_correct(tiny_root):
    out, batches = run(tiny_root)
    assert out["correct"] is True
    assert out["failed"] == 0 and out["attempted"] == round(150 * 0.25)
    assert set(out["metrics"]) == {"latency_p50_ms", "latency_p95_ms",
                                   "throughput_qps", "setup_s"}
    assert list(out["checks"]) == ["mismatched", "unanswered",
                                   "nonzero_checked"]
    assert out["checks"]["nonzero_checked"]["value"] >= 10
    assert list(out)[-1] == "checks"
    assert out["device"]["platform"] == "cpu"


def test_a_closed_loop_run_is_correct(tiny_root):
    out = harness.run_cell("tiny.closed", 77, 0.5, False, time.perf_counter(),
                           root=tiny_root, require_tpu=False,
                           compile_cache=False, workers=1)
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] >= 6
    assert out["metrics"]["throughput_qps"]["value"] > 0


@pytest.mark.parametrize("fault", ["altered", "half_batch", "stale"])
def test_a_planted_fault_makes_the_run_incorrect(tiny_root, fault):
    f = {"altered": _alter, "half_batch": _half_batch, "stale": "stale"}[fault]
    out, batches = run(tiny_root, f)
    if fault == "half_batch":
        assert max(batches) >= 2, "no batch to halve"
    assert out["correct"] is False
    assert out["checks"]["mismatched"]["value"] > 0


def _world():
    cfg = harness.load_json(ROOT, "bench", "configs", "snb-fs-p1500.json")
    return gen.generate(dict(cfg["graph"], **TINY), 3)


def test_every_seed_offers_the_same_work_in_another_order():
    g = _world()
    t = dict(loop=dict(kind="open", arrivals="poisson", rate_qps=4.0),
             templates={"Q2": 2, "Q4": 1, "Q7": 1})
    (a, da), (b, db) = (load.open_window(t, g, seed, 10.0) for seed in (1, 2))
    assert len(a) == len(b) == 40 and not np.array_equal(da, db)
    for qs in (a, b):
        names = [q["template"] for q in qs]
        assert {k: names.count(k) for k in set(names)} == {
            "Q2": 20, "Q4": 10, "Q7": 10}
    assert [q["template"] for q in a] != [q["template"] for q in b]
    assert np.all(np.diff(da) >= 0) and 0 <= da[0] and da[-1] < 10.0
    assert load.open_window(t, g, 1, 10.0)[0] == a        # seeded


def test_on_off_arrivals_burst_at_their_rate():
    g = _world()
    t = dict(loop=dict(kind="open", arrivals="onoff", rate_qps=2.0,
                       burst_qps=20.0, on_s=1.0, period_s=5.0),
             templates={"Q2": 1})
    _, due = load.open_window(t, g, 9, 10.0)
    on = (due % 5.0) < 1.0
    assert on.sum() == 40 and (~on).sum() == 16


def test_zipf_parameters_favour_a_drifting_hot_value():
    g = _world()
    pool = harness.templates.pools(g)
    rng = np.random.default_rng(0)
    zipf = dict(dist="zipf", s=2.0, drift_s=10.0)
    early = [harness.templates.Params(rng, pool, zipf, at=1.0).value("tag")
             for _ in range(300)]
    late = [harness.templates.Params(rng, pool, zipf, at=11.0).value("tag")
            for _ in range(300)]
    top = lambda xs: max(set(xs), key=xs.count)  # noqa: E731
    assert top(early) == pool["tag"][0] and top(late) == pool["tag"][1]
    assert early.count(top(early)) > 100


def test_closed_stream_sends_each_share_once_per_block():
    g = _world()
    st = load.ClosedStream(dict(templates={"Q2": 2, "Q7": 1}), g, 4)
    names = [st.next(0.1 * i)["template"] for i in range(9)]
    for k in range(3):
        block = names[3 * k:3 * k + 3]
        assert sorted(block) == ["Q2", "Q2", "Q7"]


def test_traffic_for_a_template_the_config_does_not_serve(tiny_root):
    man = harness.manifest(tiny_root)
    path = os.path.join(tiny_root, "bench", "traffic", "bad.json")
    with open(path, "w") as f:
        json.dump(dict(loop=dict(kind="open", arrivals="poisson",
                                 rate_qps=1.0), templates={"Q8": 1}), f)
    man["workloads"].append(dict(name="tiny.bad", config="tiny",
                                 traffic="bad", chips=1, why="test"))
    with pytest.raises(ValueError):
        harness.cell_files(man, "tiny.bad", tiny_root)
    os.remove(path)


def test_no_tpu_no_result():
    """On the CPU the entry point refuses: non-zero exit, no result line."""
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         harness.manifest()["workloads"][0]["name"], "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        cwd=ROOT, capture_output=True, text=True, timeout=300,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert p.returncode != 0 and p.stdout.strip() == ""
    assert "not a TPU" in p.stderr


def test_benchmark_files_alone_give_no_result(tmp_path):
    man = harness.manifest()
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    for p in man["paths"]:
        shutil.copytree(os.path.join(ROOT, p), tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    p = subprocess.run(
        [sys.executable] + man["command"][1:] + [
            "--workload", man["workloads"][0]["name"], "--seed", "1",
            "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0 and p.stdout.strip() == ""
