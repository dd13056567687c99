"""Multi-device serving conformance: the shard_map-native batched
partitioned path on a real (forced-host) device mesh must be bit-identical
to the single-device vmap simulation across the full conformance matrix.

These tests only run with >1 JAX devices; scripts/ci.sh provides them by
launching pytest with ``XLA_FLAGS=--xla_force_host_platform_device_count=8``
and selecting ``-m multidevice`` (the tier-1 default deselects the marker,
and the skipif below keeps a plain single-device run green either way).
"""
import jax
import numpy as np
import pytest

import conformance as C
from repro.core import engine_partitioned as EP
from repro.serving import BatchScheduler

pytestmark = [
    pytest.mark.multidevice,
    pytest.mark.skipif(
        jax.device_count() < 2,
        reason="needs XLA_FLAGS=--xla_force_host_platform_device_count"),
]

N_WORKERS = 8


def _case_names():
    # collection-time static mirror of test_conformance.CASE_NAMES
    from test_conformance import CASE_NAMES
    return CASE_NAMES


@pytest.fixture(scope="module")
def matrix(small_dynamic_graph):
    return C.case_matrix(small_dynamic_graph)


def _fields(r):
    return (("total", r.total), ("per_vertex", r.per_vertex),
            ("minmax", r.minmax))


@pytest.mark.parametrize("mode", C.ALL_MODES)
@pytest.mark.parametrize("name", _case_names())
def test_batched_sharded_serving_matches_vmap_simulation(
        small_dynamic_graph, matrix, name, mode):
    """One shard_map dispatch (batch × workers on the device mesh, p2p
    boundary exchange) ≡ the vmap-simulated single-device leg, bit for bit,
    for every matrix cell — served through the batch scheduler with zero
    per-query fallbacks."""
    assert N_WORKERS % jax.device_count() == 0
    case = matrix[name]
    queries = C.perturbed_batch(case.qry, 3)

    def serve(use_shard_map):
        sched = BatchScheduler(small_dynamic_graph, engine="partitioned",
                               mode=mode, n_buckets=C.N_BUCKETS,
                               n_workers=N_WORKERS, keep_outputs=True,
                               use_shard_map=use_shard_map)
        res = sched.run(queries)
        assert len(sched.last_dispatches) == 1, (name, mode, use_shard_map)
        return sched, res

    sched_sh, shard = serve(True)
    sched_sim, sim = serve(False)
    assert sched_sh.n_devices == jax.device_count() > 1
    assert sched_sim.n_devices == 1
    for i, (a, b) in enumerate(zip(shard, sim)):
        assert a.split == b.split
        for field, got in _fields(a):
            want = dict(_fields(b))[field]
            if want is None and got is None:
                continue
            assert want is not None and got is not None, (name, mode, field)
            assert np.array_equal(got, want), (name, mode, i, field)


def test_sharded_execute_matches_simulation(small_dynamic_graph, matrix):
    """The sequential (non-batched) partitioned entry also lowers the worker
    axis to the device mesh, bit-identically, for a representative slice."""
    for name in ("plain-2hop", "etr-overlaps", "agg-min"):
        case = matrix[name]
        for mode in C.ALL_MODES:
            sh = EP.execute(small_dynamic_graph, case.qry, mode=mode,
                            n_buckets=C.N_BUCKETS, n_workers=N_WORKERS,
                            use_shard_map=True)
            sim = EP.execute(small_dynamic_graph, case.qry, mode=mode,
                             n_buckets=C.N_BUCKETS, n_workers=N_WORKERS,
                             use_shard_map=False)
            for field in ("total", "per_vertex", "minmax"):
                a, b = getattr(sh, field), getattr(sim, field)
                if a is None and b is None:
                    continue
                assert np.array_equal(np.asarray(a), np.asarray(b)), \
                    (name, mode, field)


def test_exchange_is_point_to_point(small_dynamic_graph):
    """Structural acceptance: boundary traffic per hop is O(ghost entries)
    for all three channels — the lane tables cover exactly the ghosts, and
    the profiler reports those ragged volumes, never the frontier."""
    from repro.graphdata.queries import make_workload, to_minmax

    g = small_dynamic_graph
    _, arrays, _ = EP.partition_for(g, 4, None)
    frontier = 2 * g.n_edges
    assert 0 < arrays.exchange_volume() < frontier
    assert 0 < arrays.etr_exchange_volume() < frontier
    # lanes cover exactly the ghost entries (ragged content == channel volume)
    real_state_lanes = int((arrays.xchg_send_slot < arrays.v_max).sum())
    assert real_state_lanes == arrays.exchange_volume()
    real_etr_lanes = int((arrays.etr_send_slot < arrays.s_max).sum())
    assert real_etr_lanes == arrays.etr_exchange_volume()

    inst = make_workload(g, templates=("Q4",), n_per_template=1, seed=7)[0]
    prof = EP.measure_supersteps(g, inst.qry, n_workers=4, repeats=1)
    for i, ep in enumerate(inst.qry.e_preds):
        ch = prof.exchange_channels[i]
        if ep.etr_op != -1:
            assert ch[2] == arrays.etr_exchange_volume() < frontier
        else:
            assert ch[0] == arrays.exchange_volume() < frontier
    # extremum channel: rides the state lanes, doubling the state volume
    qmm = to_minmax(
        make_workload(g, templates=("Q2",), n_per_template=1, seed=8)[0],
        g).qry
    profm = EP.measure_supersteps(g, qmm, n_workers=4, repeats=1)
    assert (profm.exchange_channels[:, 1] == arrays.exchange_volume()).all()
    # ... and those are exactly the canonical per-query volumes the serving
    # bench reports (one rule, one helper)
    want = EP.query_exchange_volumes(qmm, arrays)
    got = profm.channel_totals()
    assert got == want, (got, want)
