"""Wrapper: hop layouts (static, per graph/shard) + edge-level entries.

``HopLayout`` extends ``bucket_scatter.build_layout``'s sorted-CSR block
layout with the per-block segment-boundary tables the prefix-difference
delivery reads (``seg_start``/``seg_end``: each destination's first /
one-past-last slot in its block) and device-resident mirrors, so the slot
permutation — the O(E) gathers that move per-edge operands into padded block
slots — stays inside the traced program while the layout itself is
host-static (part of the executable key, never retraced).

Block sizing: ``block_v=None`` (the default) auto-sizes ONE block covering
all destinations — the right shape for the CPU interpreter, whose per-block
operand slicing dominates multi-block grids.  TPU deployments pass an
explicit MXU/VMEM-shaped ``block_v`` (e.g. 256) and get the grid the module
docstring describes.

Three consumers:

  * the fused hop kernels (``hop_scatter.fused_hop_*``) take slot-layout
    operands prepared with ``slots()`` — the mode-specific weight prep lives
    with the state algebra in ``core/superstep.py``;
  * ``scatter_deliver`` / ``scatter_extremum`` are the delivery-only entries
    for per-edge values that must exist anyway (ETR hop outputs);
  * ``build_worker_layouts`` stacks one layout per partition shard with a
    common slot shape, so the partitioned executor can vmap (or shard_map)
    the kernel over its worker axis.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..bucket_scatter.ops import ScatterLayout, build_layout
from ..common import check_impl, resolve_interpret
from .hop_scatter import (fused_hop_cols_pallas, scatter_cols_pallas,
                          scatter_extremum_pallas)

#: keys of the device-table dict the kernels consume (``HopLayout.tables``)
TABLE_KEYS = ("gather", "valid", "ldst", "sstart", "send")

#: the VMEM-shaped block the compile probe uses (a TPU deployment's shape)
PROBE_BLOCK_V, PROBE_BLOCK_E = 256, 512


@functools.lru_cache(maxsize=None)
def _compile_probe(backend: str) -> Optional[str]:
    """Compile the fused hop kernel once for ``backend``; the compiler's
    refusal (or None when it compiles).  On the CPU the kernel runs in the
    Pallas interpreter, so there is nothing to compile."""
    if backend == "cpu":
        return None
    nb, bv, be = 2, PROBE_BLOCK_V, PROBE_BLOCK_E
    i32, f32 = jnp.int32, jnp.float32
    shapes = (jax.ShapeDtypeStruct((nb * bv + 1, 1), f32),   # state table
              jax.ShapeDtypeStruct((nb, be), i32),           # src slots
              jax.ShapeDtypeStruct((nb, be, 1), f32),        # weights
              jax.ShapeDtypeStruct((nb, bv), i32),           # seg_start
              jax.ShapeDtypeStruct((nb, bv), i32),           # seg_end
              jax.ShapeDtypeStruct((nb, be), i32))           # local_dst
    fn = functools.partial(fused_hop_cols_pallas, block_v=bv, interpret=False)
    try:
        jax.jit(fn).lower(*shapes).compile()
    except Exception as e:  # what the compiler raises differs per backend
        return f"{type(e).__name__}: {str(e).strip().splitlines()[0]}"
    return None


def kernel_refusal() -> Optional[str]:
    """Why the fused hop kernel cannot run on the default backend, or None.

    Decided by compiling the kernel at a VMEM-shaped block, once per
    backend.  Serving consults it so that no fallback hides the device:
    ``impl='auto'`` and the admission ladder offer the kernel only where
    it compiled, and an explicit ``impl='pallas'`` raises."""
    return _compile_probe(jax.default_backend())


def available_impls() -> Tuple[str, ...]:
    """The hop-delivery lowerings a planner may choose between here."""
    return ("xla",) if kernel_refusal() is not None else ("xla", "pallas")


def require_impl(impl: str) -> str:
    """Validate ``impl``; ``'pallas'`` must compile on this backend."""
    if check_impl(impl) == "pallas":
        reason = kernel_refusal()
        if reason is not None:
            raise RuntimeError(
                f"impl='pallas': the fused hop kernel does not compile on "
                f"the {jax.default_backend()} backend: {reason}")
    return impl


@dataclasses.dataclass(frozen=True)
class HopLayout:
    """A ScatterLayout plus boundary tables and device mirrors."""
    host: ScatterLayout
    gather_idx: jnp.ndarray   # int32[n_blocks * block_e]
    valid: jnp.ndarray        # bool [n_blocks * block_e]
    local_dst: jnp.ndarray    # int32[n_blocks, block_e]
    seg_start: jnp.ndarray    # int32[n_blocks, block_v]
    seg_end: jnp.ndarray      # int32[n_blocks, block_v]

    @property
    def n_blocks(self) -> int:
        return self.host.n_blocks

    @property
    def block_e(self) -> int:
        return self.host.block_e

    @property
    def block_v(self) -> int:
        return self.host.block_v

    @property
    def num_segments(self) -> int:
        return self.host.num_segments

    @property
    def tables(self) -> dict:
        """The kernels' device operands as one dict (a uniform pytree, so
        executors can vmap worker-stacked tables with ``in_axes=0``)."""
        return dict(gather=self.gather_idx, valid=self.valid,
                    ldst=self.local_dst, sstart=self.seg_start,
                    send=self.seg_end)

    def signature(self) -> tuple:
        """Hashable shape identity — the executable-cache key component."""
        return ("hop_layout", self.n_blocks, self.block_e, self.block_v,
                self.num_segments)


def _auto_block_v(num_segments: int) -> int:
    """One block over all destinations, padded to the lane width."""
    return max(128, -(-num_segments // 128) * 128)


def _boundary_tables(seg_ids: np.ndarray, host: ScatterLayout) -> tuple:
    """Per-block (seg_start, seg_end) slot positions for every destination.

    Destinations are blocked by ``v // block_v`` and edges are arrival-
    sorted, so each destination's contributions are one contiguous run of
    its block's REAL slots; empty destinations get a zero-width run."""
    nb, bv = host.n_blocks, host.block_v
    counts = np.bincount(np.asarray(seg_ids), minlength=host.num_segments)
    gend = np.cumsum(counts)
    gstart = gend - counts
    block_base = np.zeros(nb, np.int64)
    blk_counts = np.bincount(np.asarray(seg_ids) // bv, minlength=nb)
    np.cumsum(blk_counts[:-1], out=block_base[1:])
    sstart = np.zeros((nb, bv), np.int32)
    send = np.zeros((nb, bv), np.int32)
    for b in range(nb):
        vlo = b * bv
        vhi = min(vlo + bv, host.num_segments)
        sstart[b, : vhi - vlo] = gstart[vlo:vhi] - block_base[b]
        send[b, : vhi - vlo] = gend[vlo:vhi] - block_base[b]
    return sstart, send


def build_hop_layout(seg_ids: np.ndarray, num_segments: int,
                     block_v: Optional[int] = None, block_e_mult: int = 512,
                     block_e: Optional[int] = None) -> HopLayout:
    if block_v is None:
        block_v = _auto_block_v(num_segments)
    host = build_layout(seg_ids, num_segments, block_v=block_v,
                        block_e_mult=block_e_mult, block_e=block_e)
    sstart, send = _boundary_tables(seg_ids, host)
    return HopLayout(
        host,
        jnp.asarray(host.gather_idx, jnp.int32),
        jnp.asarray(host.valid),
        jnp.asarray(host.local_dst),
        jnp.asarray(sstart),
        jnp.asarray(send),
    )


def build_worker_layouts(seg_rows: np.ndarray, num_segments: int,
                         block_v: Optional[int] = None,
                         block_e_mult: int = 512) -> List[HopLayout]:
    """One layout per partition shard over a COMMON slot shape.

    ``seg_rows`` [W, Emax] are the per-worker (sorted) local destination
    arrays — pad entries carry the trash segment id (num_segments - 1), so
    they land in real slots and deliver their (zero) contributions to the
    sliced-off trash row.  Forcing one ``block_e`` across workers lets the
    executor stack the layouts and map the kernel over the worker axis.
    """
    seg_rows = np.asarray(seg_rows)
    if block_v is None:
        block_v = _auto_block_v(num_segments)
    n_blocks = -(-num_segments // block_v)
    fullest = max(
        (int(np.bincount(row // block_v, minlength=n_blocks).max(initial=1))
         for row in seg_rows), default=1)
    block_e = max(block_e_mult,
                  int(-(-fullest // block_e_mult) * block_e_mult))
    return [
        build_hop_layout(row, num_segments, block_v=block_v,
                         block_e_mult=block_e_mult, block_e=block_e)
        for row in seg_rows
    ]


def stack_layout_tables(layouts: Sequence[HopLayout]) -> dict:
    """Stack per-worker HopLayout tables into [W, ...] device tensors (the
    ``hop_``-prefixed entries of the partitioned executor's pdev dict; same
    role as the partitioner's padded per-worker tensors)."""
    assert len({(l.n_blocks, l.block_e, l.block_v) for l in layouts}) == 1
    stacked = {k: jnp.stack([l.tables[k] for l in layouts])
               for k in TABLE_KEYS}
    return {f"hop_{k}": v for k, v in stacked.items()}


def worker_tables(pdev: dict, w: Optional[slice] = None) -> dict:
    """The generic-keyed table dict back out of a pdev-style dict; ``w``
    optionally slices one worker's rows (profiling call sites)."""
    out = {k: pdev[f"hop_{k}"] for k in TABLE_KEYS}
    if w is not None:
        out = {k: v[w] for k, v in out.items()}
    return out


def slots(x: jnp.ndarray, gather_idx: jnp.ndarray, valid: jnp.ndarray, fill):
    """Permute per-edge values into (flat) block slots; pad slots → fill."""
    g = x[gather_idx]
    mask = valid
    for _ in x.shape[1:]:
        mask = mask[..., None]
    return jnp.where(mask, g, jnp.asarray(fill, x.dtype))


def scatter_deliver(
    cnt_e: jnp.ndarray,           # [E, *TS] per-edge contributions
    lt: dict,                     # HopLayout.tables (possibly worker-sliced)
    num_segments: int,
    block_v: int,
    interpret: Optional[bool] = None,
    impl: str = "pallas",
) -> jnp.ndarray:
    """Delivery-only fused reduce of already-materialised per-edge state."""
    ts = cnt_e.shape[1:]
    C = int(np.prod(ts)) if ts else 1
    cp = slots(cnt_e.reshape(cnt_e.shape[0], C), lt["gather"], lt["valid"],
               0.0)
    n_blocks, block_e = lt["ldst"].shape
    out = scatter_cols_pallas(
        cp.reshape(n_blocks, block_e, C), lt["sstart"], lt["send"], block_v,
        interpret=resolve_interpret(interpret, impl))
    return out[:num_segments].reshape((num_segments,) + ts)


def scatter_extremum(
    m_e: jnp.ndarray,             # f32[E] per-edge extremum channel
    alive_e: jnp.ndarray,         # f32/bool[E] per-edge count liveness
    lt: dict,                     # HopLayout.tables
    num_segments: int,
    block_v: int,
    neutral: float,
    op_is_min: bool,
    interpret: Optional[bool] = None,
    impl: str = "pallas",
) -> jnp.ndarray:
    """Delivery-only fused min/max of a per-edge channel (empty segments
    land on the aggregation-neutral element, like segment_min/segment_max)."""
    n_blocks, block_e = lt["ldst"].shape
    mp = slots(m_e, lt["gather"], lt["valid"], neutral).reshape(n_blocks,
                                                                block_e)
    ap = slots(alive_e.astype(jnp.float32), lt["gather"], lt["valid"], 0.0)
    out = scatter_extremum_pallas(
        mp, ap.reshape(n_blocks, block_e), lt["ldst"], block_v, neutral,
        op_is_min, interpret=resolve_interpret(interpret, impl))
    return out[:num_segments]
