"""JAX's persistent compilation cache at a fixed place.

Entry points (``chip_smoke.py``, ``launch/query.py``, the benchmarks) call
``enable_compile_cache()`` once at startup; importing a library module never
does.  The cache's path is part of its key, so it must not move between
runs: where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
nothing is set here; otherwise the cache lives at ``CACHE_DIR`` inside the
checkout (listed in ``.gitignore``).
"""
from __future__ import annotations

import os

import jax

#: the in-checkout cache directory used when the environment names none
CACHE_DIR = os.path.abspath(os.path.join(
    os.path.dirname(__file__), "..", "..", "..", ".jax_cache"))


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    return CACHE_DIR
