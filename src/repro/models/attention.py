"""Attention: GQA + RoPE + sliding-window, chunked (flash-style) for long
sequences, plus single-token decode against a (ring-buffer) KV cache.

The chunked path is the XLA-compileable analogue of the Pallas flash kernel
in ``repro.kernels.flash_attention`` — O(chunk x kv) live memory, lax.scan
over query blocks.  It is the only attention path of training and prefill on
every backend, TPU included: nothing under ``repro.models`` calls the flash
kernel.
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from repro.models.config import ModelConfig
from repro.models.layers import dense, rope

NEG_INF = -1e30


def _grouped_scores(q, k):
    """q: (b, sq, hkv, g, hd)  k: (b, skv, hkv, hd) -> (b, hkv, g, sq, skv)."""
    return jnp.einsum("bqhgd,bkhd->bhgqk", q, k,
                      preferred_element_type=jnp.float32)


def _apply_probs(p, v):
    """p: (b, hkv, g, sq, skv)  v: (b, skv, hkv, hd) -> (b, sq, hkv, g, hd)."""
    return jnp.einsum("bhgqk,bkhd->bqhgd", p.astype(v.dtype), v,
                      preferred_element_type=jnp.float32)


def _softmax(scores, mask):
    scores = jnp.where(mask, scores, NEG_INF)
    m = jnp.max(scores, axis=-1, keepdims=True)
    e = jnp.exp(scores - jax.lax.stop_gradient(m))
    return e / (jnp.sum(e, axis=-1, keepdims=True) + 1e-30)


def _mask(q_pos, kv_pos, causal: bool, window: int):
    """(sq, skv) boolean mask from absolute positions."""
    m = jnp.ones((q_pos.shape[0], kv_pos.shape[0]), bool)
    if causal:
        m &= kv_pos[None, :] <= q_pos[:, None]
    if window > 0:
        m &= kv_pos[None, :] > (q_pos[:, None] - window)
    return m


def attention(q, k, v, *, causal: bool = True, window: int = 0,
              chunk: int = 0, q_offset: int = 0,
              unroll: bool = False) -> jax.Array:
    """Full-sequence attention.

    q: (b, sq, hq, hd); k, v: (b, skv, hkv, hd).  hq must be a multiple of
    hkv (GQA).  `q_offset` is the absolute position of q[0] (prefill
    continuation); kv is assumed to start at position 0.
    """
    b, sq, hq, hd = q.shape
    hkv = k.shape[2]
    g = hq // hkv
    scale = hd ** -0.5
    q = (q * scale).reshape(b, sq, hkv, g, hd)

    if chunk and sq > chunk and sq % chunk == 0:
        return _chunked(q, k, v, causal=causal, window=window, chunk=chunk,
                        q_offset=q_offset,
                        unroll=unroll).reshape(b, sq, hq, hd)

    q_pos = q_offset + jnp.arange(sq)
    kv_pos = jnp.arange(k.shape[1])
    mask = _mask(q_pos, kv_pos, causal, window)
    scores = _grouped_scores(q, k)
    probs = _softmax(scores, mask[None, None, None])
    return _apply_probs(probs, v).astype(q.dtype).reshape(b, sq, hq, hd)


def _chunked(q, k, v, *, causal, window, chunk, q_offset, unroll=False):
    """lax.scan over query chunks; windowed attention slices kv statically.

    q: (b, sq, hkv, g, hd) pre-scaled.  Returns (b, sq, hkv, g, hd).
    """
    b, sq, hkv, g, hd = q.shape
    skv = k.shape[1]
    nc = sq // chunk
    qc = q.reshape(b, nc, chunk, hkv, g, hd).transpose(1, 0, 2, 3, 4, 5)

    kv_window = 0
    if window > 0:
        # Each query chunk only ever sees the last `window + chunk` kv slots.
        kv_window = min(skv, window + chunk)

    def body(_, args):
        idx, qi = args  # qi: (b, chunk, hkv, g, hd)
        start = idx * chunk + q_offset
        q_pos = start + jnp.arange(chunk)
        if kv_window:
            kv_start = jnp.clip(start + chunk - kv_window, 0, skv - kv_window)
            ki = jax.lax.dynamic_slice_in_dim(k, kv_start, kv_window, axis=1)
            vi = jax.lax.dynamic_slice_in_dim(v, kv_start, kv_window, axis=1)
            kv_pos = kv_start + jnp.arange(kv_window)
        else:
            ki, vi = k, v
            kv_pos = jnp.arange(skv)
        mask = (kv_pos[None, :] <= q_pos[:, None]) if causal else \
            jnp.ones((chunk, kv_pos.shape[0]), bool)
        if window > 0:
            mask &= kv_pos[None, :] > (q_pos[:, None] - window)
        scores = _grouped_scores(qi, ki)
        probs = _softmax(scores, mask[None, None, None])
        out = _apply_probs(probs, vi).astype(qi.dtype)
        return None, out

    if unroll:
        outs = jnp.stack([body(None, (jnp.asarray(i), qc[i]))[1]
                          for i in range(nc)])
    else:
        _, outs = jax.lax.scan(body, None, (jnp.arange(nc), qc))
    return outs.transpose(1, 0, 2, 3, 4, 5).reshape(b, sq, hkv, g, hd)


def decode_attention(q, k_cache, v_cache, n_valid) -> jax.Array:
    """One-token attention against a cache.

    q: (b, 1, hq, hd); caches: (b, S, hkv, hd) with `n_valid` filled slots.
    `n_valid` may be a scalar (shared position, legacy batch-1 decode) or a
    (b,) vector (per-slot positions, the batched serve path — rows with
    ``n_valid == 0`` attend to nothing and emit zeros through the softmax
    epsilon).  Cache slot order is irrelevant (keys stored post-RoPE), so
    ring-buffer rotation needs no unpermute.
    """
    b, _, hq, hd = q.shape
    hkv = k_cache.shape[2]
    g = hq // hkv
    qg = (q * hd ** -0.5).reshape(b, 1, hkv, g, hd)
    scores = _grouped_scores(qg, k_cache)            # (b, hkv, g, 1, S)
    S = k_cache.shape[1]
    n_valid = jnp.asarray(n_valid)
    if n_valid.ndim == 0:
        mask = (jnp.arange(S) < n_valid)[None, None, None, None, :]
    else:
        mask = (jnp.arange(S)[None, :] <
                n_valid[:, None])[:, None, None, None, :]
    probs = _softmax(scores, mask)
    out = _apply_probs(probs, v_cache).astype(q.dtype)
    return out.reshape(b, 1, hq, hd)


# ---------------------------------------------------------------------------
# Projection wrappers
# ---------------------------------------------------------------------------

def project_qkv(x, p, cfg: ModelConfig, positions, use_rope: bool = True):
    b, s, _ = x.shape
    q = dense(x, p["wq"], p.get("bq")).reshape(b, s, cfg.num_heads, cfg.head_dim)
    k = dense(x, p["wk"], p.get("bk")).reshape(b, s, cfg.num_kv_heads, cfg.head_dim)
    v = dense(x, p["wv"], p.get("bv")).reshape(b, s, cfg.num_kv_heads, cfg.head_dim)
    if use_rope:
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions, cfg.rope_theta)
    return q, k, v


def merge_heads_out(o, p):
    b, s = o.shape[:2]
    return dense(o.reshape(b, s, -1), p["wo"])


def self_attention(x, p, cfg: ModelConfig, *, positions=None, causal=True,
                   window: Optional[int] = None, use_rope=True):
    """Training / prefill self-attention over the whole sequence."""
    b, s, _ = x.shape
    if positions is None:
        positions = jnp.arange(s)[None, :]
    q, k, v = project_qkv(x, p, cfg, positions, use_rope)
    if cfg.attn_kv_gather:
        # gather K/V across the model axis once per layer; the chunked dot
        # then runs on full-seq kv locally instead of emitting per-chunk
        # partial-sum all-reduces (SP attention; EXPERIMENTS §Perf).
        from repro.parallel.ctx import shard_activation

        k = shard_activation(k, "kv_rep")
        v = shard_activation(v, "kv_rep")
    w = cfg.attention_window if window is None else window
    o = attention(q, k, v, causal=causal, window=w, chunk=cfg.attn_chunk,
                  unroll=cfg.unroll_loops)
    return merge_heads_out(o, p), (k, v)


def cross_attention(x, p, cfg: ModelConfig, k, v):
    b, s, _ = x.shape
    q = dense(x, p["wq"], p.get("bq")).reshape(b, s, cfg.num_heads, cfg.head_dim)
    o = attention(q, k, v, causal=False, window=0, chunk=0)
    return merge_heads_out(o, p)


def decode_self_attention(x, p, cfg: ModelConfig, cache, layer_cache_idx=None,
                          use_rope=True):
    """x: (b, 1, d).  cache: dict with k/v (b, S, hkv, hd), pos (scalar int32
    shared across the batch, or a (b,) per-slot position vector).

    Writes the new kv at slot pos % S (ring buffer for windowed caches) and
    attends over min(pos + 1, S) valid slots — per row when pos is a vector
    (slot `b` writes at pos[b] % S), which is what lets a fixed-width batched
    executor decode mixed-length requests in one call.
    """
    b = x.shape[0]
    S = cache["k"].shape[1]
    pos = jnp.asarray(cache["pos"])
    if pos.ndim == 0:
        positions = jnp.full((b, 1), pos, jnp.int32)
        q, k, v = project_qkv(x, p, cfg, positions, use_rope)
        slot = pos % S
        k_cache = jax.lax.dynamic_update_slice_in_dim(
            cache["k"], k.astype(cache["k"].dtype), slot, axis=1)
        v_cache = jax.lax.dynamic_update_slice_in_dim(
            cache["v"], v.astype(cache["v"].dtype), slot, axis=1)
    else:
        positions = pos[:, None].astype(jnp.int32)
        q, k, v = project_qkv(x, p, cfg, positions, use_rope)
        # one-hot masked write: row b lands at its own slot pos[b] % S
        oh = (jnp.arange(S)[None, :] == (pos % S)[:, None])[:, :, None, None]
        k_cache = jnp.where(oh, k.astype(cache["k"].dtype), cache["k"])
        v_cache = jnp.where(oh, v.astype(cache["v"].dtype), cache["v"])
    n_valid = jnp.minimum(pos + 1, S)
    o = decode_attention(q, k_cache, v_cache, n_valid)
    new_cache = {"k": k_cache, "v": v_cache, "pos": pos}
    return merge_heads_out(o, p), new_cache
