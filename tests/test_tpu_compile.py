"""Compile rehearsals for a TPU v5e: the served executables, compiled for a
described chip that is not attached.

JAX ships the TPU compiler, and ``jax.experimental.topologies`` describes a
v5e host without one.  These tests compile what ``chip_smoke.py`` runs, at
its sizes, so that what the TPU compiler refuses (tiling, VMEM, device
memory) shows up here at no chip time:

  (a) the dense batched executable, static mode, batch 8, at phase A's
      shapes, for an ETR and a non-ETR template, within one chip's 16 GiB;
  (b) the same in bucket mode at phase B's shapes, for Q8 and for the
      template that needs the most memory;
  (c) the partitioned executable over four described devices at 1k
      persons, whose compiled text holds the boundary exchange's
      all-to-all.

Shapes for (a) and (b) come from a small proxy graph whose vertex and
traversal-edge axes and property-slot counts are widened to those of
``generate_ldbc(LdbcParams(n_persons=N, degree_dist="facebook", seed=0))``
at phase A's and phase B's person counts (generating those graphs takes a
minute or more; their sizes are recorded below).

The topology is described inside a module-scoped fixture, never at import:
describing it loads the TPU library, which only one process may hold.
"""
import os

import jax
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

from repro.core import engine as E
from repro.core import engine_partitioned as EP
from repro.core import intervals as iv
from repro.graphdata.ldbc import LdbcParams, generate_ldbc
from repro.graphdata.queries import make_workload
from repro.serving.compile import compile_plan_tensor

HBM_BYTES = 16 * 2**30      # one v5e chip
BATCH = 8
N_BUCKETS = 16

#: the full graphs' sizes (seed 0): vertices, traversal edges (2E), and
#: property slots per key id
PHASE_A = dict(n_persons=100_000, dynamic=False, V=1_380_000, T=7_834_806,
               slots={0: 1, 1: 1, 2: 1, 3: 10, 4: 1, 5: 8, 6: 1, 7: 1})
PHASE_B = dict(n_persons=20_000, dynamic=True, V=276_000, T=1_568_444,
               slots={0: 1, 1: 3, 2: 1, 3: 10, 4: 3, 5: 7, 6: 1, 7: 1})


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    # the TPU compiler's threads inherit this thread's CPUs: hold them to
    # two, so that in a parallel test run these compiles do not starve
    # the other workers, some of whose tests are timing-sensitive
    cpus = os.sched_getaffinity(0)
    os.sched_setaffinity(0, sorted(cpus)[:2])
    prev = jax.config.jax_enable_compilation_cache
    try:
        try:
            t = topologies.get_topology_desc(platform="tpu",
                                             topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a persistent cache cannot read back a compile for a described chip
        jax.config.update("jax_enable_compilation_cache", False)
        yield t
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)
        os.sched_setaffinity(0, cpus)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _proxy(dynamic: bool):
    return generate_ldbc(LdbcParams(n_persons=1000, degree_dist="facebook",
                                    dynamic=dynamic, seed=0))


def widened(gdev: dict, proxy, full: dict, sharding) -> dict:
    """Shape structs of ``gdev`` at the full graph's sizes: the proxy's
    vertex (V, V+1) and traversal-edge (2E) axes and property-slot counts
    take the full graph's values."""
    V, T = proxy.n_vertices, int(proxy.traversal["t_dst"].shape[0])
    size = {V: full["V"], V + 1: full["V"] + 1, T: full["T"]}

    def widen(path, a):
        shape = [size.get(d, d) for d in a.shape]
        if path[0].key == "vprops":
            shape[1] = full["slots"][path[1].key]
        return jax.ShapeDtypeStruct(tuple(shape), a.dtype, sharding=sharding)

    return jax.tree_util.tree_map_with_path(widen, gdev)


def _batch_args(proxy, qry, sharding):
    params = compile_plan_tensor([qry] * BATCH).params
    bedges = iv.bucket_edges(proxy.lifespan[0], proxy.lifespan[1],
                             N_BUCKETS)
    return (jax.ShapeDtypeStruct(params.shape, params.dtype,
                                 sharding=sharding),
            jax.ShapeDtypeStruct(bedges.shape, bedges.dtype,
                                 sharding=sharding))


def _dense_compile(full: dict, template: str, mode: int, sharding):
    proxy = _proxy(full["dynamic"])
    qry = make_workload(proxy, templates=(template,), n_per_template=1,
                        seed=0)[0].qry
    split = 0 if qry.agg_op != -1 else qry.n_vertices - 1
    fn = E.batch_executable(proxy, qry, split, mode, N_BUCKETS,
                            sliced=False).fn
    gdev = widened(E._prepare_gdev(proxy), proxy, full, sharding)
    params, bedges = _batch_args(proxy, qry, sharding)
    return fn.lower(gdev, params, bedges).compile()


def _fits(compiled) -> int:
    m = compiled.memory_analysis()
    used = m.argument_size_in_bytes + m.temp_size_in_bytes
    assert used < HBM_BYTES, (m.argument_size_in_bytes, m.temp_size_in_bytes)
    return used


@pytest.mark.parametrize("template", ["Q7", "Q2"])
def test_dense_static_phase_a(one_chip, template):
    """(a) phase A's dense executable compiles and fits one chip."""
    _fits(_dense_compile(PHASE_A, template, E.MODE_STATIC, one_chip))


@pytest.mark.parametrize("template", ["Q8", "Q6"])
def test_dense_bucket_phase_b(one_chip, template):
    """(b) phase B's bucket-mode executable compiles and fits: Q8, and Q6,
    whose default split needs the most device memory of any template and
    split at this size (12.4 GiB; at 30k persons several refuse)."""
    _fits(_dense_compile(PHASE_B, template, E.MODE_BUCKET, one_chip))


def test_partitioned_four_devices(topo, monkeypatch):
    """(c) the shard_map program over four described devices: compiles,
    fits, and exchanges boundary state with all-to-all."""
    import repro.launch.mesh as mesh_mod

    mesh = Mesh(np.asarray(topo.devices[:4]), ("workers",))
    monkeypatch.setattr(mesh_mod, "make_worker_mesh", lambda n: mesh)
    proxy = _proxy(False)
    qry = make_workload(proxy, templates=("Q7",), n_per_template=1,
                        seed=0)[0].qry
    gdev, pdev, _ = EP.device_tables(proxy, 4)
    rep, wrk = NamedSharding(mesh, P()), NamedSharding(mesh, P("workers"))
    sds = lambda tree, sh: jax.tree_util.tree_map(  # noqa: E731
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sh), tree)
    fn = EP._plan_fn(qry, qry.n_vertices - 1, E.MODE_STATIC, N_BUCKETS, 4,
                     batched=True)
    params, bedges = _batch_args(proxy, qry, rep)
    compiled = fn.lower(sds(gdev, rep), sds(pdev, wrk), params,
                        bedges).compile()
    _fits(compiled)
    assert "all-to-all" in compiled.as_text()
