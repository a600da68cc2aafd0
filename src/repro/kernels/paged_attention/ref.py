"""Pure-jnp oracle for the paged-attention decode kernel.

Writes the step's K/V into the stacked pool with an XLA scatter, then
gathers the layer's pages back into a dense (b, hkv, nb * block_tokens,
d) view via the block tables and runs masked single-query attention —
mathematically the kernel's online softmax, without the paging.  Also
the XLA-compiled fallback path the batched serve executor uses off-TPU
(the gather jits to a plain dynamic-gather + matmul, no Pallas
interpreter in the loop).
"""
from __future__ import annotations

import jax.numpy as jnp

NEG_INF = -1e30


def paged_attention_ref(q, k, v, k_pages, v_pages, layer, block_tables,
                        lengths, *, window: int = 0):
    """Same contract as :func:`..paged_attention.paged_attention`."""
    b, hq, d = q.shape
    _, hkv, n_pages, _, block_tokens = k_pages.shape
    g = hq // hkv
    nb = block_tables.shape[1]
    skv = nb * block_tokens

    # row r's new K/V goes to column (len - 1) % bt of its page; an
    # inactive row names page n_pages, which the scatter drops
    pos = jnp.maximum(lengths - 1, 0)
    page = jnp.where(lengths > 0,
                     block_tables[jnp.arange(b), pos // block_tokens],
                     n_pages)
    off = pos % block_tokens
    k_pages = k_pages.at[layer, :, page, :, off].set(
        k.astype(k_pages.dtype), mode="drop")
    v_pages = v_pages.at[layer, :, page, :, off].set(
        v.astype(v_pages.dtype), mode="drop")

    # (hkv, b, nb, d, bt) -> (b, hkv, skv, d): pages in table order are
    # positions in ascending order, matching the dense cache layout
    def dense(pages):
        return pages[layer][:, block_tables].transpose(1, 0, 2, 4, 3) \
            .reshape(b, hkv, skv, d).astype(jnp.float32)

    kd, vd = dense(k_pages), dense(v_pages)
    qg = q.reshape(b, hkv, g, d).astype(jnp.float32) * d ** -0.5
    s = jnp.einsum("bhgd,bhkd->bhgk", qg, kd)

    kv_pos = jnp.arange(skv)[None, None, None, :]
    ln = lengths[:, None, None, None]
    mask = kv_pos < ln
    if window > 0:
        mask &= kv_pos > (ln - 1 - window)
    s = jnp.where(mask, s, NEG_INF)

    m = jnp.max(s, axis=-1, keepdims=True)
    e = jnp.where(mask, jnp.exp(s - m), 0.0)
    p = e / jnp.maximum(jnp.sum(e, axis=-1, keepdims=True), 1e-30)
    o = jnp.einsum("bhgk,bhkd->bhgd", p, vd)
    return o.reshape(b, hq, d).astype(q.dtype), k_pages, v_pages
