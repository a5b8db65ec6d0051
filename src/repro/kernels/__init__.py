"""Pallas TPU kernels for the compute hot-spots HiHGNN optimizes:

* fused_fp_coeff   — FP fused with attention-coefficient computation
                     (paper Alg. 2 lines 7-8)
* flash_attention  — the same online-softmax insight on dense attention
                     (LM architectures; windowed for local attention)
* seg_gat_agg_multigraph — fused NA: block-sparse online-softmax
                     aggregation (the paper's stage-fusion datapath +
                     softmax decomposition, Fig. 6/7) and the multi-lane
                     execution (§4.2) in one kernel: work units from
                     different semantic graphs dispatched via
                     scalar-prefetched (graph_id, dst_row) tables; one
                     graph is the G=1 case
* seg_gat_agg_fused_fp — the stage-fusion megakernel (Alg. 2): the
                     multigraph launch with FP pulled inside — raw
                     feature tiles projected on-chip, h' never
                     materialized (DESIGN.md §10)
"""
from . import ops
from .ops import flash_attention, fused_fp_coeff
from .seg_gat_agg_fused_fp import fused_fp_na_reference, seg_gat_agg_fused_fp
from .seg_gat_agg_multigraph import seg_gat_agg_multigraph

__all__ = [
    "ops",
    "flash_attention",
    "fused_fp_coeff",
    "fused_fp_na_reference",
    "seg_gat_agg_fused_fp",
    "seg_gat_agg_multigraph",
]
