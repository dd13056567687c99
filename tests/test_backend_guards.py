"""No fallback hides the device, and the entry points' start-up rules.

* the scheduler's ``impl`` slot accepts only ``'xla'``, the one
  hop-delivery lowering, and the admission ladder decides the same way
  whatever the backend is called;
* ``launch/query.py`` exits non-zero when a dispatch raises a real error;
* the persistent compilation cache sits where ``JAX_COMPILATION_CACHE_DIR``
  says, else at one fixed path inside the checkout;
* ``chip_smoke.py`` refuses to run without a TPU or without the repo, and
  its phases run end to end at a tiny size.

Tests that need another backend name steer it by monkeypatching
``jax.default_backend`` inside the test.
"""
import importlib.util
import os
import shutil
import subprocess
import sys

import jax
import pytest

from repro.graphdata.queries import make_workload
from repro.launch import compile_cache
from repro.serving import AdmissionPolicy, BatchScheduler
from repro.serving.testing import FakeDispatcher

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
SMOKE = os.path.join(ROOT, "chip_smoke.py")


def _on(monkeypatch, backend: str) -> None:
    if backend != "cpu":
        monkeypatch.setattr(jax, "default_backend", lambda: backend)


@pytest.mark.parametrize("value", ["xla", "auto", "pallas",
                                   "pallas_interpret"])
def test_scheduler_impl_slot(small_static_graph, value):
    """``impl`` names the one hop-delivery lowering: ``'xla'`` is accepted
    and every other value raises at construction."""
    if value == "xla":
        sched = BatchScheduler(small_static_graph, impl=value,
                               dispatcher=FakeDispatcher())
        wl = make_workload(small_static_graph, templates=("Q2",),
                           n_per_template=1, seed=0)
        assert [r.status for r in sched.run(wl)] == ["done"]
        return
    with pytest.raises(ValueError, match="impl must be 'xla'"):
        BatchScheduler(small_static_graph, impl=value)


@pytest.mark.parametrize("backend", ["cpu", "tpu"])
def test_admission_ladder_backend_neutral(monkeypatch, medium_static_graph,
                                          backend):
    """The same overloaded submits decide the same way under either backend
    name: the sliced and quantum rungs degrade the first, the second is
    rejected, and no rung names a lowering."""
    _on(monkeypatch, backend)
    inst = make_workload(medium_static_graph, templates=("Q2",),
                         n_per_template=1, seed=46)[0]
    sched = BatchScheduler(medium_static_graph, engine="dense",
                           dispatcher=FakeDispatcher(),
                           admission=AdmissionPolicy(headroom=1.0))
    split = inst.qry.n_vertices - 1
    cost_s = sched._planner.estimate(inst.qry, split).t_ms / 1e3
    first = sched.submit(inst, deadline_s=0.9 * cost_s, now=0.0)
    second = sched.submit(inst, deadline_s=0.5 * cost_s, now=0.0)
    got = [(d.action, d.rungs, d.engine, d.max_batch)
           for d in (first, second)]
    assert got == [("degrade", ("engine=sliced", "quantum=8"), "sliced", 8),
                   ("reject", (), None, None)]
    assert first.predicted_s == pytest.approx(0.7 * cost_s, rel=1e-12)
    assert not any(r.startswith("impl=") for d in (first, second)
                   for r in d.rungs)


@pytest.mark.parametrize("mode,fail", [("--serve", True), ("--replay", True),
                                       ("--serve", False)])
def test_query_cli_exit_code(monkeypatch, mode, fail):
    """A dispatch that raises a real error (here RESOURCE_EXHAUSTED) ends
    the CLI non-zero; the same run without it exits normally."""
    from repro.launch import query

    if fail:
        def refuse(self, *a, **k):
            raise RuntimeError("RESOURCE_EXHAUSTED: out of device memory")
        monkeypatch.setattr(BatchScheduler, "_dispatch_jax", refuse)
    monkeypatch.setattr(query, "enable_compile_cache", lambda: None)
    monkeypatch.setattr(sys, "argv", ["query", "--persons", "40",
                                      "--queries", "1", mode])
    if fail:
        with pytest.raises(SystemExit) as ei:
            query.main()
        assert ei.value.code not in (0, None)
        assert "queries failed" in str(ei.value.code)
    else:
        query.main()


def test_compile_cache_env_sets_nothing(monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "cache-from-env")
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache() == "cache-from-env"
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_fixed_in_checkout(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        path = compile_cache.enable_compile_cache()
        assert path == jax.config.jax_compilation_cache_dir
        assert path == os.path.join(ROOT, ".jax_cache")
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    with open(os.path.join(ROOT, ".gitignore")) as f:
        assert "/.jax_cache/" in f.read().split()


def _run_smoke(path: str) -> subprocess.CompletedProcess:
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, path], capture_output=True,
                          text=True, env=env, timeout=120)


def test_chip_smoke_refuses_the_cpu():
    p = _run_smoke(SMOKE)
    assert p.returncode != 0
    assert "no TPU found" in p.stderr
    assert '"ok"' not in p.stdout


def test_chip_smoke_needs_the_repo(tmp_path):
    alone = tmp_path / "chip_smoke.py"
    shutil.copy(SMOKE, alone)
    p = _run_smoke(str(alone))
    assert p.returncode != 0
    assert '"ok"' not in p.stdout


def _smoke_module():
    spec = importlib.util.spec_from_file_location("chip_smoke", SMOKE)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("phase", ["a", "b"])
def test_chip_smoke_phases_tiny(phase, capsys):
    """The smoke's phases end to end on the CPU at a tiny size: every
    query done, the oracle agrees, the required checks are met."""
    smoke = _smoke_module()
    if phase == "a":
        smoke.phase_a(0, n_persons=600, n_per_template=2)
    else:
        smoke.phase_b(0, n_persons=300, n_per_template=2)
    out = capsys.readouterr().out
    assert "all done" in out and "oracle checked" in out


@pytest.mark.multidevice
def test_chip_smoke_partitioned_phase(capsys):
    """The four-chip phase's logic on forced host devices."""
    if jax.device_count() < 2:
        pytest.skip("needs XLA_FLAGS=--xla_force_host_platform_device_count")
    _smoke_module().phase_partitioned(0, n_persons=600,
                                      n_workers=jax.device_count(),
                                      n_per_template=2)
    assert "all-to-all" in capsys.readouterr().out
