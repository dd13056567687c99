"""No fallback hides the device, and the entry points' start-up rules.

* ``impl='auto'`` and the admission ladder offer the fused hop kernel only
  on a backend where it compiles, and an explicit ``impl='pallas'`` there
  raises at construction with the compiler's reason;
* ``launch/query.py`` exits non-zero when a dispatch raises a real error;
* the persistent compilation cache sits where ``JAX_COMPILATION_CACHE_DIR``
  says, else at one fixed path inside the checkout;
* ``chip_smoke.py`` refuses to run without a TPU or without the repo, and
  its phases run end to end at a tiny size.

Tests that need another backend steer it by monkeypatching
``jax.default_backend`` inside the test: the kernel probe then compiles the
kernel for that backend on this host's CPU, which the compiler refuses just
as the TPU compiler does today.
"""
import importlib.util
import os
import shutil
import subprocess
import sys

import jax
import pytest

from repro.core import engine as E
from repro.core import engine_partitioned as EP
from repro.graphdata.queries import make_workload
from repro.kernels import hop_scatter as HK
from repro.launch import compile_cache
from repro.serving import AdmissionPolicy, BatchScheduler
from repro.serving.testing import FakeDispatcher

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
SMOKE = os.path.join(ROOT, "chip_smoke.py")


def _on(monkeypatch, backend: str) -> None:
    if backend != "cpu":
        monkeypatch.setattr(jax, "default_backend", lambda: backend)


def test_cpu_offers_the_kernel():
    assert jax.default_backend() == "cpu"
    assert HK.kernel_refusal() is None
    assert HK.available_impls() == ("xla", "pallas")


@pytest.mark.parametrize("backend,want", [("cpu", "pallas"), ("tpu", "xla")])
def test_auto_impl_offers_only_what_compiles(monkeypatch, medium_static_graph,
                                             backend, want):
    """With the kernel priced cheaper, 'auto' picks it where it compiles
    and never where it does not."""
    _on(monkeypatch, backend)
    wl = make_workload(medium_static_graph, templates=("Q2", "Q4"),
                       n_per_template=2, seed=7)
    sched = BatchScheduler(medium_static_graph, engine="dense", impl="auto",
                           dispatcher=FakeDispatcher())
    sched._planner.coeffs["theta_scatter_xla"] = 10.0
    res = sched.run(wl)
    assert all(r.status == "done" for r in res)
    assert {d.impl for d in sched.last_dispatches} == {want}


@pytest.mark.parametrize("backend,want", [("cpu", "degrade"),
                                          ("tpu", "reject")])
def test_admission_ladder_offers_only_what_compiles(
        monkeypatch, medium_static_graph, backend, want):
    """The cheaper-impl rung is the only one enabled: it degrades onto the
    kernel where the kernel compiles, and rejects where it does not."""
    _on(monkeypatch, backend)
    inst = make_workload(medium_static_graph, templates=("Q2",),
                         n_per_template=1, seed=46)[0]
    pol = AdmissionPolicy(headroom=1.0, degrade_impls=("xla", "pallas"),
                          allow_engine_downgrade=False,
                          degrade_max_batch=None)
    sched = BatchScheduler(medium_static_graph, dispatcher=FakeDispatcher(),
                           admission=pol)
    sched._planner.coeffs["theta_scatter_xla"] = 10.0
    split = inst.qry.n_vertices - 1
    c_xla = sched._planner.estimate(inst.qry, split, "xla").t_ms / 1e3
    dec = sched.submit(inst, deadline_s=0.9 * c_xla, now=0.0)
    assert dec.action == want
    assert ("impl=pallas" in dec.rungs) == (want == "degrade")


@pytest.mark.parametrize("entry", ["scheduler", "dense", "partitioned"])
def test_explicit_pallas_raises_where_it_does_not_compile(
        monkeypatch, small_static_graph, entry):
    _on(monkeypatch, "tpu")
    qry = make_workload(small_static_graph, templates=("Q2",),
                        n_per_template=1, seed=0)[0].qry
    build = {
        "scheduler": lambda: BatchScheduler(small_static_graph,
                                            impl="pallas"),
        "dense": lambda: E.batch_executable(small_static_graph, qry,
                                            impl="pallas"),
        "partitioned": lambda: EP.batch_executable(small_static_graph, qry,
                                                   impl="pallas"),
    }[entry]
    with pytest.raises(RuntimeError,
                       match=r"does not compile on the tpu backend: \w+"):
        build()


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
