from .hop_scatter import (fused_hop_cols_pallas, fused_hop_interval_pallas,
                          scatter_cols_pallas, scatter_extremum_pallas)
from .ops import (TABLE_KEYS, HopLayout, available_impls, build_hop_layout,
                  build_worker_layouts, kernel_refusal, require_impl,
                  scatter_deliver, scatter_extremum, slots,
                  stack_layout_tables, worker_tables)
from .ref import fused_hop_cols_ref, fused_hop_interval_ref

__all__ = [
    "TABLE_KEYS", "HopLayout", "available_impls", "kernel_refusal",
    "require_impl", "build_hop_layout", "build_worker_layouts",
    "stack_layout_tables", "worker_tables", "slots", "scatter_deliver",
    "scatter_extremum", "fused_hop_cols_pallas", "fused_hop_interval_pallas",
    "scatter_cols_pallas", "scatter_extremum_pallas", "fused_hop_cols_ref",
    "fused_hop_interval_ref",
]
