"""Public jit'd entry points for the Pallas kernels.

Every op takes ``interpret=`` so the TPU kernel body can be validated on
CPU (interpret mode executes the kernel in Python).  ``ref``-suffixed
oracles live in ref.py; tests sweep shapes/dtypes and assert_allclose.
"""
from __future__ import annotations

from .flash_attention import flash_attention
from .fused_fp_coeff import fused_fp_coeff
from .ref import ref_flash_attention, ref_fused_fp_coeff, ref_seg_gat_agg

__all__ = [
    "flash_attention",
    "fused_fp_coeff",
    "ref_flash_attention",
    "ref_fused_fp_coeff",
    "ref_seg_gat_agg",
]
