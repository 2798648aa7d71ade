"""Shared Pallas block-size selection for the TPU kernels in this package.

Every fused kernel faces the same question: the largest lane/sublane block
that (a) is a multiple of 8 (the TPU sublane width, guide: tiling
constraints), (b) divides the padded extent so the grid needs no ragged
masking, and (c) does not exceed a preferred size chosen for VMEM. The
attention kernels (`attention.py`), the int8 decode-attention kernels
(`decode_attention.py`) and the int8 matmul (`int8_matmul.py`) all use
this one resolver — one definition of "legal block" instead of three
drifting copies.
"""
from __future__ import annotations


def pick_block(width: int, preferred: int = 128) -> int:
    """Largest multiple of 8 (TPU sublane) <= `preferred` that divides
    `width`; falls back to the full width (always a legal block)."""
    block = min(preferred, width) // 8 * 8
    while block >= 8:
        if width % block == 0:
            return block
        block -= 8
    return width
