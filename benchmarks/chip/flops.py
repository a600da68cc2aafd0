"""Operations and bytes that the algorithm needs, counted from shapes.

These are what the work requires, not what the program happens to
compute: a decode step counts its live rows at their own lengths, not
the padded batch.  The counts per token, per position and per row are the
configuration's family's (``family.py``).
"""
from __future__ import annotations

from typing import Dict

import family


def matmul_params(c: Dict) -> int:
    """Weights multiplied per token, active ones only, and the head (the
    embedding lookup is not a product)."""
    return family.of(c).matmul_params(c)


def decode_flops(c: Dict, rows: int, sum_len: int) -> float:
    """One decode step: ``rows`` live requests whose sequence lengths
    (the new token included) add up to ``sum_len``."""
    attn = family.of(c).attn_flops_per_position(c) * sum_len
    return 2.0 * matmul_params(c) * rows + attn


def paged_attn_flops(c: Dict, sum_len: int) -> float:
    """The paged-attention kernel over every layer of one decode step:
    scores and the weighted sum of values, at the live lengths."""
    return float(family.of(c).attn_flops_per_position(c) * sum_len)


def paged_attn_bytes(c: Dict, rows: int, sum_len: int) -> float:
    """Bytes the kernel must move over every layer of one decode step:
    the live K and V positions, each query read and output written, and
    each row's length."""
    fam = family.of(c)
    return float(fam.kv_bytes_per_position(c) * sum_len
                 + fam.row_bytes(c) * rows)
