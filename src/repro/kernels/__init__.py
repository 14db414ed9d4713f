# Pallas TPU kernels for the compute hot-spots: segment_spmm (GNN
# aggregation) plus its ragged/GAT/segment-max variants in fused_gnn.py,
# flash_attention, ssd_scan (Mamba-2).  They compile with Mosaic on a TPU
# and run in interpret mode elsewhere (mode.py).  Block sizes resolve
# through the deterministic autotuner in autotune.py.
from repro.kernels.autotune import DEFAULT_CONFIG, KernelConfig, get_tuned
from repro.kernels.ops import (
    gnn_aggregate,
    gnn_gat_aggregate,
    gnn_gather_aggregate,
    gnn_segment_max,
    mha_attention,
    ssd_scan,
)

__all__ = [
    "gnn_aggregate",
    "gnn_gather_aggregate",
    "gnn_gat_aggregate",
    "gnn_segment_max",
    "mha_attention",
    "ssd_scan",
    "KernelConfig",
    "DEFAULT_CONFIG",
    "get_tuned",
]
