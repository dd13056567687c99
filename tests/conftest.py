import os
import sys

# Tests run on the single real CPU device (the 512-device override is ONLY in
# launch/dryrun.py, per the assignment).
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import gc

import numpy as np
import pytest

from repro.graphdata.ldbc import LdbcParams, generate_ldbc


@pytest.fixture(autouse=True, scope="module")
def _bound_jax_jit_footprint():
    """Keep the process's compiled-code footprint bounded: one pytest process
    compiles hundreds of XLA:CPU executables, and with an older jaxlib
    (0.4.x) `pytest -x -q` died in backend_compile ~175 tests in, on a test
    that passes alone.  The installed jax/jaxlib is 0.9; the bound stays
    because a single process still accumulates every executable it ever
    compiled.  Executable references drop at module boundaries only;
    within a module nothing is evicted, so steady-state caching behavior
    (and everything the serving tests assert about cache hits) is
    untouched."""
    import jax
    jax.clear_caches()
    gc.collect()
    yield


@pytest.fixture(scope="session")
def small_static_graph():
    return generate_ldbc(LdbcParams(n_persons=60, seed=3, dynamic=False))


@pytest.fixture(scope="session")
def small_dynamic_graph():
    return generate_ldbc(LdbcParams(n_persons=40, seed=5, dynamic=True))


@pytest.fixture(scope="session")
def medium_static_graph():
    return generate_ldbc(LdbcParams(n_persons=200, seed=9, dynamic=False))
