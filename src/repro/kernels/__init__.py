"""Pallas TPU kernels for the model layers (``models/``).

Each kernel package provides:
  <name>.py — the pl.pallas_call with explicit BlockSpec VMEM tiling
  ops.py    — the jit'd public wrapper (padding, GQA mapping, impl selection)
  ref.py    — the pure-jnp oracle used by the test sweeps

Implementation selection is uniform across packages (``common.py``):
``impl='xla' | 'pallas' | 'pallas_interpret'`` plus an ``interpret`` flag
that defaults to AUTO — interpreter mode only when the backend is CPU, so a
GPU/TPU run can never silently execute a kernel in interpreter mode.

Hot-spots covered:
  flash_attention — blocked online-softmax GQA attention w/ causal + sliding
                    window (LM train/prefill)
  embedding_bag   — fused gather + segment-reduce over huge tables (DLRM)

The query engine has no kernel here: its hop delivery is plain XLA
(``core/superstep.deliver``).
"""
from . import embedding_bag, flash_attention  # noqa: F401
from .common import IMPLS, resolve_interpret, use_pallas  # noqa: F401
