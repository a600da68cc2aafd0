"""The dense decoder family: grouped-query attention and a SwiGLU MLP in
every layer, all layers alike and stacked.

Layout: the program's dense-decoder parameter tree (``embed.tok``,
``blocks.*`` stacked over ``num_layers``, ``final_norm``, ``lm_head`` when
the head is untied).  As in the program, a leaf of two or more dimensions
as stored (a matrix, or a vector stacked over layers) takes the served
dtype.

Reference layer: RMSNorm, rotary position embedding (half-split rotation,
as HF Llama and Qwen2), grouped-query attention with a causal mask and a
full softmax, optional biases on Q/K/V, and a SwiGLU MLP.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from family import Leaf
from reference import HI, _mm, _q8, _rmsnorm, _rope

BF16 = 2


def layout(c: Dict) -> Dict[str, Leaf]:
    d, ff, V, L = c["d_model"], c["d_ff"], c["vocab_size"], c["num_layers"]
    q = c["num_heads"] * c["head_dim"]
    kv = c["num_kv_heads"] * c["head_dim"]
    leaves = [
        ("embed.tok", (V, d), "embed", 0),
        ("final_norm", (d,), "norm", 0),
        ("blocks.ln1", (d,), "norm", L),
        ("blocks.ln2", (d,), "norm", L),
        ("blocks.attn.wq", (d, q), "fan_in", L),
        ("blocks.attn.wk", (d, kv), "fan_in", L),
        ("blocks.attn.wv", (d, kv), "fan_in", L),
        ("blocks.attn.wo", (q, d), "fan_in", L),
        ("blocks.mlp.wi", (d, ff), "fan_in", L),
        ("blocks.mlp.wg", (d, ff), "fan_in", L),
        ("blocks.mlp.wo", (ff, d), "fan_in", L),
    ]
    if c.get("qkv_bias"):
        leaves += [("blocks.attn.bq", (q,), "bias", L),
                   ("blocks.attn.bk", (kv,), "bias", L),
                   ("blocks.attn.bv", (kv,), "bias", L)]
    if not c.get("tie_embeddings"):
        leaves.append(("lm_head", (d, V), "fan_in", 0))
    return {name: Leaf(shape, stack > 0 or len(shape) >= 2, init, stack)
            for name, shape, init, stack in leaves}


def layer_leaves(c: Dict, i: int) -> Dict[str, Tuple[str, Optional[int]]]:
    """Layer ``i`` is entry ``i`` of every stacked leaf, named without
    ``blocks.``."""
    return {name[len("blocks."):]: (name, i)
            for name, leaf in layout(c).items() if leaf.stack}


def block(x, w: Dict[str, jax.Array], c: Dict, quant: bool):
    """One decoder layer over x: (b, s, d) float32."""
    b, s, _ = x.shape
    hq, hkv, hd = c["num_heads"], c["num_kv_heads"], c["head_dim"]
    pos = jnp.arange(s)
    h = _rmsnorm(x, w["ln1"], c["norm_eps"])
    q = _mm(h, w["attn.wq"], quant)
    k = _mm(h, w["attn.wk"], quant)
    v = _mm(h, w["attn.wv"], quant)
    if "attn.bq" in w:
        q, k, v = q + w["attn.bq"], k + w["attn.bk"], v + w["attn.bv"]
    q = _rope(q.reshape(b, s, hq, hd), pos, c["rope_theta"])
    k = _rope(k.reshape(b, s, hkv, hd), pos, c["rope_theta"])
    v = v.reshape(b, s, hkv, hd)
    k = jnp.repeat(k, hq // hkv, axis=2)
    v = jnp.repeat(v, hq // hkv, axis=2)
    if quant:
        q, k, v = _q8(q, -1), _q8(k, -1), _q8(v, 1)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=HI) * hd ** -0.5
    causal = pos[None, :] <= pos[:, None]
    scores = jnp.where(causal, scores, -jnp.inf)
    p = jax.nn.softmax(scores, axis=-1)
    if quant:
        p = _q8(p, -1)
    o = jnp.einsum("bhqk,bkhd->bqhd", p, v, precision=HI).reshape(b, s, -1)
    x = x + _mm(o, w["attn.wo"], quant)
    h = _rmsnorm(x, w["ln2"], c["norm_eps"])
    ff = jax.nn.silu(_mm(h, w["mlp.wg"], quant)) * _mm(h, w["mlp.wi"], quant)
    return x + _mm(ff, w["mlp.wo"], quant)


def matmul_params(c: Dict) -> int:
    """Every layer's projections and MLP, and the head (the embedding
    lookup is not a product)."""
    d, ff, V = c["d_model"], c["d_ff"], c["vocab_size"]
    q = c["num_heads"] * c["head_dim"]
    kv = c["num_kv_heads"] * c["head_dim"]
    per_layer = d * q + 2 * d * kv + q * d + 3 * d * ff
    return c["num_layers"] * per_layer + d * V


def attn_flops_per_position(c: Dict) -> int:
    """Scores and the weighted sum of values, every query head."""
    return 4 * c["num_layers"] * c["num_heads"] * c["head_dim"]


def kv_bytes_per_position(c: Dict) -> int:
    """K and V of every kv head, in bf16."""
    return c["num_layers"] * 2 * c["num_kv_heads"] * c["head_dim"] * BF16


def row_bytes(c: Dict) -> int:
    """Each query read and output written (bf16), and the row's length."""
    return c["num_layers"] * (2 * c["num_heads"] * c["head_dim"] * BF16 + 4)


def reference_row_bytes(c: Dict, pad_to: int) -> int:
    """Scores, softmax and mask, float32, of every query head."""
    return c["num_heads"] * pad_to * pad_to * 4 * 3
