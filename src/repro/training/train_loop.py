"""Train-step factories: pjit path (GSPMD) and explicit shard_map DP path.

* ``make_train_step`` — the production path: loss+grad+AdamW in one jitted
  function; sharding comes from in_shardings/out_shardings at the call site
  (launch/dryrun.py, launch/train.py).  Supports microbatch gradient
  accumulation (sequential lax.scan over microbatches).
* ``make_dp_train_step`` — explicit data-parallel shard_map variant with a
  real ``lax.psum`` gradient exchange, where gradient *compression* (int8 /
  top-k with error feedback) is applied.  Used by the compression tests and
  the weak-scaling bench.
"""
from __future__ import annotations

from functools import partial
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .compression import CompressionCfg, compressed_psum, init_error_state
from .optimizer import OptCfg, apply_updates, init_state


def make_train_step(
    loss_fn: Callable[[Any, Dict], jnp.ndarray],
    opt_cfg: OptCfg,
    microbatches: int = 1,
    donate: bool = True,
):
    """loss_fn(params, batch) → scalar.  Returns jitted step fn."""

    def step(params, opt_state, batch):
        if microbatches == 1:
            loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        else:
            def mb(carry, mbatch):
                acc_loss, acc_grads = carry
                l, g = jax.value_and_grad(loss_fn)(params, mbatch)
                acc_grads = jax.tree_util.tree_map(jnp.add, acc_grads, g)
                return (acc_loss + l, acc_grads), None

            split = jax.tree_util.tree_map(
                lambda x: x.reshape(microbatches, x.shape[0] // microbatches,
                                    *x.shape[1:]),
                batch,
            )
            zeros = jax.tree_util.tree_map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params)
            (loss, grads), _ = jax.lax.scan(mb, (jnp.float32(0.0), zeros), split)
            loss = loss / microbatches
            grads = jax.tree_util.tree_map(lambda g: g / microbatches, grads)
        new_params, new_state, metrics = apply_updates(opt_cfg, params, grads, opt_state)
        return new_params, new_state, dict(loss=loss, **metrics)

    donate_argnums = (0, 1) if donate else ()
    return jax.jit(step, donate_argnums=donate_argnums)


def make_dp_train_step(
    loss_fn: Callable[[Any, Dict], jnp.ndarray],
    opt_cfg: OptCfg,
    mesh,
    compression: Optional[CompressionCfg] = None,
    axis: str = "data",
):
    """Explicit shard_map DP step with (optionally compressed) psum."""
    comp = compression or CompressionCfg(kind="none")

    def step(params, opt_state, err, batch):
        def shard_fn(params, opt_state, err, batch):
            loss, grads = jax.value_and_grad(loss_fn)(params, batch)
            grads, new_err = compressed_psum(comp, grads, err, axis)
            loss = jax.lax.pmean(loss, axis)
            new_params, new_state, metrics = apply_updates(
                opt_cfg, params, grads, opt_state)
            return new_params, new_state, new_err, dict(loss=loss, **metrics)

        pspec_rep = jax.tree_util.tree_map(lambda _: P(), params)
        ospec_rep = jax.tree_util.tree_map(lambda _: P(), opt_state)
        espec_rep = jax.tree_util.tree_map(lambda _: P(), err)
        bspec = jax.tree_util.tree_map(lambda _: P(axis), batch)
        return jax.shard_map(
            shard_fn, mesh=mesh,
            in_specs=(pspec_rep, ospec_rep, espec_rep, bspec),
            out_specs=(pspec_rep, ospec_rep, espec_rep,
                       dict(loss=P(), lr=P(), grad_norm=P())),
            check_vma=False,
        )(params, opt_state, err, batch)

    return jax.jit(step)


def train_state_init(params, opt_cfg: OptCfg, with_err: bool = False):
    st = init_state(params)
    if with_err:
        return st, init_error_state(params)
    return st
