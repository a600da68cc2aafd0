"""Operations and bytes that the algorithm needs, counted from shapes.

These are what the work requires, not what the program happens to
compute: a decode step counts its live rows at their own lengths, not
the padded batch.
"""
from __future__ import annotations

from typing import Dict

BF16 = 2


def matmul_params(c: Dict) -> int:
    """Weights multiplied per token: every layer's projections and MLP,
    and the head (the embedding lookup is not a product)."""
    d, ff, V = c["d_model"], c["d_ff"], c["vocab_size"]
    q = c["num_heads"] * c["head_dim"]
    kv = c["num_kv_heads"] * c["head_dim"]
    per_layer = d * q + 2 * d * kv + q * d + 3 * d * ff
    return c["num_layers"] * per_layer + d * V


def decode_flops(c: Dict, rows: int, sum_len: int) -> float:
    """One decode step: ``rows`` live requests whose sequence lengths
    (the new token included) add up to ``sum_len``."""
    attn = 4 * c["num_layers"] * c["num_heads"] * c["head_dim"] * sum_len
    return 2.0 * matmul_params(c) * rows + attn


def paged_attn_flops(c: Dict, sum_len: int) -> float:
    """The paged-attention kernel over every layer of one decode step:
    scores and the weighted sum of values, at the live lengths."""
    return 4.0 * c["num_layers"] * c["num_heads"] * c["head_dim"] * sum_len


def paged_attn_bytes(c: Dict, rows: int, sum_len: int) -> float:
    """Bytes the kernel must move over every layer of one decode step:
    the live K and V positions (bf16), each query read and output
    written (bf16), and each row's length."""
    L, hq, hkv, hd = (c["num_layers"], c["num_heads"], c["num_kv_heads"],
                      c["head_dim"])
    kv = 2 * hkv * hd * BF16 * sum_len
    qo = 2 * rows * hq * hd * BF16
    return float(L * (kv + qo + 4 * rows))
