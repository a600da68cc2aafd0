"""Decoder-only LM engine: training loss, prefill, and KV-cache decode for
the dense / MoE / hybrid (RG-LRU) / SSM (RWKV6) / VLM-backbone families.

Layer stacks are ``lax.scan``-ed over stacked parameters when homogeneous
(cfg.scan_layers) and unrolled otherwise (hybrid pattern, first-k-dense, and
cost-reference compiles).  Activation sharding constraints come from the
ambient :mod:`repro.parallel.ctx`.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models import rglru, rwkv
from repro.models.attention import decode_self_attention, self_attention
from repro.models.config import ModelConfig
from repro.models.layers import (dense, embed_tokens, lm_logits, mlp, norm,
                                 softmax_xent)
from repro.models.moe import moe_block
from repro.parallel.ctx import shard_activation

PyTree = Any


# ---------------------------------------------------------------------------
# Blocks (training / prefill)
# ---------------------------------------------------------------------------

def decoder_block(x, bp, cfg: ModelConfig, *, moe: bool, dense_ffn_p=None,
                  collect_kv: bool = False):
    """Pre-norm decoder block. Returns (x, aux_loss, (k, v) | None)."""
    x = shard_activation(x, "act")
    h = norm(x, bp, "ln1", cfg)
    attn_out, kv = self_attention(h, bp["attn"], cfg,
                                  use_rope=cfg.family != "encdec")
    x = x + attn_out
    h = norm(x, bp, "ln2", cfg)
    aux = jnp.zeros((), jnp.float32)
    if moe:
        ff, aux = moe_block(h, bp["moe"], cfg)
    else:
        ff = mlp(h, dense_ffn_p or bp["mlp"], cfg)
    x = x + ff
    return x, aux, (kv if collect_kv else None)


def hybrid_block(x, bp, cfg: ModelConfig, layer_idx: int, state=None,
                 collect_state: bool = False):
    """RecurrentGemma block: RG-LRU or local attention + GeGLU MLP."""
    x = shard_activation(x, "act")
    h = norm(x, bp, "ln1", cfg)
    new_state = None
    if "attn" in bp:
        out, kv = self_attention(h, bp["attn"], cfg,
                                 window=cfg.attention_window)
        if collect_state:
            w = min(cfg.attention_window or x.shape[1], x.shape[1])
            new_state = {"k": kv[0][:, -w:], "v": kv[1][:, -w:]}
    else:
        out, new_state = rglru.recurrent_block(h, bp["rec"], cfg, state)
        if not collect_state:
            new_state = None
    x = x + out
    h = norm(x, bp, "ln2", cfg)
    x = x + mlp(h, bp["mlp"], cfg)
    return x, new_state


def rwkv_block(x, bp, cfg: ModelConfig, state=None, collect_state=False,
               unroll=False):
    from repro.models.layers import rmsnorm

    x = shard_activation(x, "act")
    h = rmsnorm(x, bp["ln1"], cfg.norm_eps)
    tm_out, tm_state = rwkv.time_mix(
        h, bp["tm"], cfg, state["tm"] if state else None,
        unroll=unroll or cfg.unroll_loops)
    x = x + tm_out
    h = rmsnorm(x, bp["ln2"], cfg.norm_eps)
    cm_out, cm_state = rwkv.channel_mix(h, bp["cm"], cfg,
                                        state["cm"] if state else None)
    x = x + cm_out
    return x, ({"tm": tm_state, "cm": cm_state} if collect_state else None)


# ---------------------------------------------------------------------------
# Stack runner
# ---------------------------------------------------------------------------

def _tree_slice(tree, i):
    return jax.tree.map(lambda a: a[i], tree)


def _tree_slice_dyn(tree, i):
    """Dynamic (traced-index) slice of a stacked param tree."""
    return jax.tree.map(
        lambda a: jax.lax.dynamic_index_in_dim(a, i, 0, keepdims=False), tree)


def run_stack(x, params, cfg: ModelConfig, collect_caches: bool = False):
    """Run the full block stack. Returns (hidden, aux_loss, caches)."""
    caches: Dict[str, Any] = {}
    aux_total = jnp.zeros((), jnp.float32)

    if cfg.family == "hybrid":
        layer_states = []
        for i in range(cfg.num_layers):
            bp = params["layers"][str(i)]
            block = functools.partial(hybrid_block, cfg=cfg, layer_idx=i,
                                      collect_state=collect_caches)
            if cfg.remat:
                block = jax.checkpoint(block)
            x, st = block(x, bp)
            layer_states.append(st)
        if collect_caches:
            caches["layers"] = layer_states
        return x, aux_total, caches

    if cfg.family == "ssm":
        def body(carry, bp):
            h, aux = carry
            h, st = rwkv_block(h, bp, cfg, collect_state=collect_caches)
            return (h, aux), st

        if cfg.remat:
            body = jax.checkpoint(body)
        if cfg.scan_layers:
            (x, aux_total), states = jax.lax.scan(
                body, (x, aux_total), params["blocks"])
        else:
            states = []
            for i in range(cfg.num_layers):
                (x, aux_total), st = body((x, aux_total),
                                          _tree_slice(params["blocks"], i))
                states.append(st)
            if collect_caches and states:
                states = jax.tree.map(lambda *xs: jnp.stack(xs), *states)
        if collect_caches:
            caches["blocks"] = states
        return x, aux_total, caches

    # dense / moe / vlm
    for i in range(cfg.first_k_dense):
        bp = params["dense_layers"][str(i)]
        block = functools.partial(decoder_block, cfg=cfg, moe=False,
                                  collect_kv=collect_caches)
        if cfg.remat:
            block = jax.checkpoint(block)
        x, _, kv = block(x, bp)
        if collect_caches:
            caches.setdefault("dense_layers", []).append(kv)

    is_moe = cfg.num_experts > 0

    def body(carry, bp):
        h, aux = carry
        h, a, kv = decoder_block(h, bp, cfg, moe=is_moe,
                                 collect_kv=collect_caches)
        return (h, aux + a), kv

    if cfg.remat:
        body = jax.checkpoint(body)
    if cfg.scan_layers:
        (x, aux_total), kvs = jax.lax.scan(body, (x, aux_total),
                                           params["blocks"])
    else:
        kvs = []
        n = cfg.num_layers - cfg.first_k_dense
        for i in range(n):
            (x, aux_total), kv = body((x, aux_total),
                                      _tree_slice(params["blocks"], i))
            kvs.append(kv)
        if collect_caches and kvs and kvs[0] is not None:
            kvs = jax.tree.map(lambda *xs: jnp.stack(xs), *kvs)
    if collect_caches:
        caches["blocks"] = kvs
    return x, aux_total, caches


# ---------------------------------------------------------------------------
# Embedding front-ends
# ---------------------------------------------------------------------------

def embed_inputs(params, batch, cfg: ModelConfig):
    """Token (+ patch) embedding. Returns (x, label_offset)."""
    tokens = shard_activation(batch["tokens"], "tokens")
    x = embed_tokens(tokens, params["embed"]["tok"], cfg.compute_dtype)
    x = x * jnp.asarray(cfg.d_model ** 0.5, cfg.compute_dtype) \
        if cfg.family == "hybrid" else x
    if cfg.family == "vlm":
        patches = batch["patches"].astype(cfg.compute_dtype)
        x = jnp.concatenate([patches, x], axis=1)
        return shard_activation(x, "act"), patches.shape[1]
    return shard_activation(x, "act"), 0


# ---------------------------------------------------------------------------
# Public entry points
# ---------------------------------------------------------------------------

def loss_fn(params, batch, cfg: ModelConfig):
    """Causal LM loss. batch: tokens (b, s) [+ patches (b, p, d) for vlm]."""
    x, patch_len = embed_inputs(params, batch, cfg)
    x, aux, _ = run_stack(x, params, cfg)
    x = norm(x, params, "final_norm", cfg)
    # predict tokens[1:] from positions [patch_len : -1] of the stream
    h = x[:, patch_len:-1] if patch_len else x[:, :-1]
    labels = batch["tokens"][:, 1:]
    if cfg.loss_chunk and h.shape[1] % cfg.loss_chunk == 0 \
            and h.shape[1] > cfg.loss_chunk:
        loss = _chunked_xent(h, labels, params, cfg)
    else:
        logits = lm_logits(h, params, cfg)
        logits = shard_activation(logits, "logits")
        loss = softmax_xent(logits, labels)
    metrics = {"xent": loss, "aux": aux}
    if cfg.num_experts > 0:
        loss = loss + 0.01 * aux
    return loss, metrics


def ring_place(kv, seq_end: int, s_slots: int, seq_axis: int):
    """Arrange kv entries so absolute position p lands in slot p % S.

    ``kv`` holds consecutive positions ending at ``seq_end - 1`` along
    ``seq_axis``.  The decode step writes the token at position `pos` into
    slot ``pos % S`` — this placement makes prefill and decode agree, and
    makes the overwritten slot always the oldest position (windowed caches).
    """
    n = kv.shape[seq_axis]
    m = min(n, s_slots)
    sl = [slice(None)] * kv.ndim
    sl[seq_axis] = slice(n - m, n)
    part = kv[tuple(sl)]
    if m < s_slots:
        pad = [(0, 0)] * kv.ndim
        pad[seq_axis] = (0, s_slots - m)
        part = jnp.pad(part, pad)
    shift = (seq_end - m) % s_slots
    if shift:
        part = jnp.roll(part, shift, axis=seq_axis)
    return part


def _chunked_xent(h, labels, params, cfg: ModelConfig):
    """Cross-entropy over seq chunks: the (b, chunk, vocab) logits tile is
    the only live logits buffer (memory-term lever; see EXPERIMENTS §Perf)."""
    b, s, d = h.shape
    c = cfg.loss_chunk
    nc = s // c
    hc = h.reshape(b, nc, c, d).transpose(1, 0, 2, 3)
    lc = labels.reshape(b, nc, c).transpose(1, 0, 2)

    def body(carry, inp):
        hi, li = inp
        logits = lm_logits(hi, params, cfg)
        logits = shard_activation(logits, "logits")
        return carry + softmax_xent(logits, li), None

    total, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32), (hc, lc))
    return total / nc


def prefill(params, batch, cfg: ModelConfig, max_len: int = 0):
    """Forward over a prompt; returns (last-token logits, decode cache).

    ``max_len`` sizes the decode cache (prompt + new tokens); defaults to
    prompt length + 64.
    """
    x, patch_len = embed_inputs(params, batch, cfg)
    seq = x.shape[1]
    max_len = max_len or seq + 64
    x, _, caches = run_stack(x, params, cfg, collect_caches=True)
    x = norm(x, params, "final_norm", cfg)
    logits = lm_logits(x[:, -1:], params, cfg)[:, 0]
    cache = _caches_to_decode_cache(caches, cfg, seq, max_len, x.shape[0])
    return logits, cache


def _caches_to_decode_cache(caches, cfg: ModelConfig, seq: int, max_len: int,
                            batch: int):
    """Convert prefill-collected kv/state into the decode cache layout.

    The cache carries a per-slot position vector ``pos`` of shape (batch,)
    — after a shared-prompt prefill all rows start equal, but decode may
    advance them independently (the batched serve executor does).
    """
    window = cfg.attention_window or max_len
    s_slots = min(window, max_len)

    def trim(kv, seq_axis):
        k, v = kv
        return {
            "k": shard_activation(
                ring_place(k.astype(cfg.compute_dtype), seq, s_slots, seq_axis),
                "cache" if seq_axis == 1 else "cache"),
            "v": shard_activation(
                ring_place(v.astype(cfg.compute_dtype), seq, s_slots, seq_axis),
                "cache"),
        }

    out: Dict[str, Any] = {"pos": jnp.full((batch,), seq, jnp.int32)}
    if cfg.family == "hybrid":
        w = min(cfg.attention_window, max_len)
        layers = {}
        for i, st in enumerate(caches["layers"]):
            if "h" in st:      # recurrent state passes through unchanged
                layers[str(i)] = st
            else:              # hybrid_block already trimmed toward window
                layers[str(i)] = {
                    "k": ring_place(st["k"].astype(cfg.compute_dtype), seq, w, 1),
                    "v": ring_place(st["v"].astype(cfg.compute_dtype), seq, w, 1),
                }
        out["layers"] = layers
        return out
    if cfg.family == "ssm":
        out["blocks"] = caches["blocks"]
        return out
    if "dense_layers" in caches:
        out["dense_layers"] = {
            str(i): trim(kv, 1) for i, kv in enumerate(caches["dense_layers"])}
    # stacked kv from scan: (L, b, s, hkv, hd) — seq axis 2
    k_st, v_st = caches["blocks"]
    out["blocks"] = {
        "k": ring_place(k_st.astype(cfg.compute_dtype), seq, s_slots, 2),
        "v": ring_place(v_st.astype(cfg.compute_dtype), seq, s_slots, 2),
    }
    return out


def init_cache(cfg: ModelConfig, batch: int, seq_len: int,
               abstract: bool = False):
    """Decode cache pytree (or ShapeDtypeStructs when abstract=True)."""
    window = cfg.attention_window or seq_len
    s_slots = min(window, seq_len)
    hkv, hd = cfg.num_kv_heads, cfg.head_dim
    dt = cfg.compute_dtype

    def arr(shape, dtype):
        return (jax.ShapeDtypeStruct(shape, dtype) if abstract
                else jnp.zeros(shape, dtype))

    cache: Dict[str, Any] = {"pos": arr((batch,), jnp.int32)}
    if cfg.family == "hybrid":
        layers = {}
        for i in range(cfg.num_layers):
            if cfg.is_attention_layer(i):
                w = min(cfg.attention_window, seq_len)
                layers[str(i)] = {"k": arr((batch, w, hkv, hd), dt),
                                  "v": arr((batch, w, hkv, hd), dt)}
            else:
                layers[str(i)] = {
                    "conv": arr((batch, cfg.conv_width - 1, cfg.lru_width), dt),
                    "h": arr((batch, cfg.lru_width), jnp.float32),
                }
        cache["layers"] = layers
        return cache
    if cfg.family == "ssm":
        h, n = cfg.rwkv_heads, cfg.rwkv_head_dim
        L = cfg.num_layers
        cache["blocks"] = {
            "tm": {"last": arr((L, batch, cfg.d_model), dt),
                   "s": arr((L, batch, h, n, n), jnp.float32)},
            "cm": {"last": arr((L, batch, cfg.d_model), dt)},
        }
        return cache
    if cfg.family == "encdec":
        from repro.models import whisper

        return whisper.init_cache(cfg, batch, seq_len, abstract)
    n_scanned = cfg.num_layers - cfg.first_k_dense
    for i in range(cfg.first_k_dense):
        cache.setdefault("dense_layers", {})[str(i)] = {
            "k": arr((batch, s_slots, hkv, hd), dt),
            "v": arr((batch, s_slots, hkv, hd), dt),
        }
    if cfg.decode_unroll:
        cache["layers"] = {
            str(i): {"k": arr((batch, s_slots, hkv, hd), dt),
                     "v": arr((batch, s_slots, hkv, hd), dt)}
            for i in range(n_scanned)
        }
        return cache
    cache["blocks"] = {"k": arr((n_scanned, batch, s_slots, hkv, hd), dt),
                       "v": arr((n_scanned, batch, s_slots, hkv, hd), dt)}
    return cache


# ---------------------------------------------------------------------------
# Paged decode (block-table KV storage; see repro.kernels.paged_attention)
# ---------------------------------------------------------------------------

def paged_kv_shape(cfg: ModelConfig, n_pages: int, block_tokens: int):
    """Page-pool tensor shape for one replica: every layer's KV lives in
    one stacked pool so a single block table addresses all layers; each
    page is a (head_dim, block_tokens) tile, tokens in the lanes."""
    return (cfg.num_layers, cfg.num_kv_heads, n_pages, cfg.head_dim,
            block_tokens)


def _full_stack_kv(cache, cfg: ModelConfig):
    """(L, b, S, hkv, hd) stacked KV from a dense/moe decode cache.

    Valid only for un-windowed caches (S == max_len), where ring_place is
    the identity for seq <= S and slot index == absolute position.
    """
    parts_k, parts_v = [], []
    for i in range(cfg.first_k_dense):
        st = cache["dense_layers"][str(i)]
        parts_k.append(st["k"][None])
        parts_v.append(st["v"][None])
    if "layers" in cache:                       # decode_unroll layout
        for i in range(cfg.num_layers - cfg.first_k_dense):
            st = cache["layers"][str(i)]
            parts_k.append(st["k"][None])
            parts_v.append(st["v"][None])
    else:
        parts_k.append(cache["blocks"]["k"])
        parts_v.append(cache["blocks"]["v"])
    return (jnp.concatenate(parts_k, 0) if len(parts_k) > 1 else parts_k[0],
            jnp.concatenate(parts_v, 0) if len(parts_v) > 1 else parts_v[0])


def scatter_prefill_pages(cache, cfg: ModelConfig, k_pages, v_pages,
                          page_ids, offs):
    """Scatter a batch-1 prefill cache into the paged KV pool.

    ``page_ids``/``offs`` are (s,) int32 for absolute positions 0..s-1 —
    position p goes to ``(page_ids[p], offs[p])`` per the block-table ABI.
    Returns the updated (k_pages, v_pages), shape
    ``paged_kv_shape(cfg, n_pages, block_tokens)``.
    """
    k_st, v_st = _full_stack_kv(cache, cfg)     # (L, 1, S, hkv, hd)
    s = page_ids.shape[0]
    kv_k = k_st[:, 0, :s].transpose(1, 0, 2, 3)  # (s, L, hkv, hd)
    kv_v = v_st[:, 0, :s].transpose(1, 0, 2, 3)
    k_pages = k_pages.at[:, :, page_ids, :, offs].set(
        kv_k.astype(k_pages.dtype))
    v_pages = v_pages.at[:, :, page_ids, :, offs].set(
        kv_v.astype(v_pages.dtype))
    return k_pages, v_pages


def paged_decode_step(params, token, lengths, k_pages, v_pages, block_tables,
                      cfg: ModelConfig, *, attn_impl: str = "auto",
                      interpret: bool = False):
    """One batched decode step over paged KV storage.

    token: (b,) int32 (last sampled token per row); lengths: (b,) int32
    valid positions per row *including* the token written this step
    (the engine's ``append_token`` runs first), so the new KV is written
    at absolute position ``lengths - 1`` and attention spans ``lengths``
    positions.  ``lengths == 0`` marks an inactive batch row: it writes
    nothing into the pool, and its logits are garbage the caller must
    mask.  Fixed shapes in, fixed shapes out — admission/detach never
    recompiles.  Each layer's paged-attention call writes its K/V rows
    into the stacked pool in place and reads the layer by index: no
    per-layer slice of the pool is ever taken.

    Returns (logits (b, V), k_pages, v_pages).
    """
    from repro.kernels.paged_attention.ops import paged_attention_decode

    positions = jnp.maximum(lengths - 1, 0)[:, None].astype(jnp.int32)
    window = cfg.attention_window or 0
    use_rope = cfg.family != "encdec"

    x = embed_tokens(token[:, None], params["embed"]["tok"], cfg.compute_dtype)

    def attn_layer(h, bp, li, kp, vp):
        """li: page-pool layer index (dense layers first, then blocks)."""
        from repro.models.attention import merge_heads_out, project_qkv

        h = shard_activation(h, "act")
        hn = norm(h, bp, "ln1", cfg)
        q, k, v = project_qkv(hn, bp["attn"], cfg, positions, use_rope)
        o, kp, vp = paged_attention_decode(
            q[:, 0], k[:, 0], v[:, 0], kp, vp, li, block_tables, lengths,
            window=window, impl=attn_impl, interpret=interpret)
        return h + merge_heads_out(o[:, None], bp["attn"]), kp, vp

    for i in range(cfg.first_k_dense):
        bp = params["dense_layers"][str(i)]
        x, k_pages, v_pages = attn_layer(x, bp, jnp.asarray(i),
                                         k_pages, v_pages)
        hn = norm(x, bp, "ln2", cfg)
        x = x + mlp(hn, bp["mlp"], cfg)

    is_moe = cfg.num_experts > 0
    n_layers = cfg.num_layers - cfg.first_k_dense
    base = cfg.first_k_dense

    def body(i, carry):
        h, kp, vp = carry
        bp = _tree_slice_dyn(params["blocks"], i)
        h, kp, vp = attn_layer(h, bp, base + i, kp, vp)
        hn = norm(h, bp, "ln2", cfg)
        if is_moe:
            ff, _ = moe_block(hn, bp["moe"], cfg)
        else:
            ff = mlp(hn, bp["mlp"], cfg)
        return h + ff, kp, vp

    if cfg.unroll_loops:
        carry = (x, k_pages, v_pages)
        for i in range(n_layers):
            carry = body(jnp.asarray(i), carry)
        x, k_pages, v_pages = carry
    else:
        x, k_pages, v_pages = jax.lax.fori_loop(
            0, n_layers, body, (x, k_pages, v_pages))

    x = norm(x, params, "final_norm", cfg)
    logits = lm_logits(x[:, -1], params, cfg)
    return logits, k_pages, v_pages


def decode_step(params, token, cache, cfg: ModelConfig):
    """One decode step. token: (b,) int32. Returns (logits (b, V), cache)."""
    x = embed_tokens(token[:, None], params["embed"]["tok"], cfg.compute_dtype)
    if cfg.family == "hybrid":
        x = x * jnp.asarray(cfg.d_model ** 0.5, cfg.compute_dtype)
    # pos: scalar (legacy shared position) or (b,) per-slot vector
    pos = jnp.asarray(cache["pos"])
    new_cache: Dict[str, Any] = {"pos": pos + 1}

    if cfg.family == "hybrid":
        new_layers = {}
        for i in range(cfg.num_layers):
            bp = params["layers"][str(i)]
            st = cache["layers"][str(i)]
            x = shard_activation(x, "act")
            h = norm(x, bp, "ln1", cfg)
            if "attn" in bp:
                lc = dict(st)
                lc["pos"] = pos
                out, lc = decode_self_attention(h, bp["attn"], cfg, lc)
                new_layers[str(i)] = {"k": lc["k"], "v": lc["v"]}
            else:
                out, new_st = rglru.recurrent_block(h, bp["rec"], cfg, st)
                new_layers[str(i)] = new_st
            x = x + out
            h = norm(x, bp, "ln2", cfg)
            x = x + mlp(h, bp["mlp"], cfg)
        new_cache["layers"] = new_layers

    elif cfg.family == "ssm":
        def body(h, inp):
            bp, st = inp
            h, new_st = rwkv_block(h, bp, cfg, state=st, collect_state=True)
            return h, new_st

        if cfg.unroll_loops:
            sts = []
            for i in range(cfg.num_layers):
                x, st = body(x, (_tree_slice(params["blocks"], i),
                                 _tree_slice(cache["blocks"], i)))
                sts.append(st)
            states = jax.tree.map(lambda *xs: jnp.stack(xs), *sts)
        else:
            x, states = jax.lax.scan(body, x,
                                     (params["blocks"], cache["blocks"]))
        new_cache["blocks"] = states

    else:
        for i in range(cfg.first_k_dense):
            bp = params["dense_layers"][str(i)]
            st = dict(cache["dense_layers"][str(i)])
            st["pos"] = pos
            x = shard_activation(x, "act")
            h = norm(x, bp, "ln1", cfg)
            out, st = decode_self_attention(h, bp["attn"], cfg, st)
            x = x + out
            h = norm(x, bp, "ln2", cfg)
            x = x + mlp(h, bp["mlp"], cfg)
            new_cache.setdefault("dense_layers", {})[str(i)] = {
                "k": st["k"], "v": st["v"]}

        is_moe = cfg.num_experts > 0
        if cfg.decode_unroll:
            # unrolled layers + per-leaf caches: each donated (k, v) pair
            # aliases straight through to the output (no while-loop carry).
            new_layers = {}
            n = cfg.num_layers - cfg.first_k_dense
            for i in range(n):
                bp = _tree_slice(params["blocks"], i)
                st = dict(cache["layers"][str(i)])
                st["pos"] = pos
                x = shard_activation(x, "act")
                h = norm(x, bp, "ln1", cfg)
                out, st = decode_self_attention(h, bp["attn"], cfg, st)
                x = x + out
                h = norm(x, bp, "ln2", cfg)
                if is_moe:
                    ff, _ = moe_block(h, bp["moe"], cfg)
                else:
                    ff = mlp(h, bp["mlp"], cfg)
                x = x + ff
                new_layers[str(i)] = {"k": st["k"], "v": st["v"]}
            new_cache["layers"] = new_layers
            x = norm(x, params, "final_norm", cfg)
            logits = lm_logits(x[:, -1], params, cfg)
            return logits, new_cache
        ks0 = cache["blocks"]["k"]
        vs0 = cache["blocks"]["v"]
        b = x.shape[0]
        s_slots = ks0.shape[2]
        slot = pos % s_slots
        n_valid = jnp.minimum(pos + 1, s_slots)
        n_layers = ks0.shape[0]
        vec_pos = pos.ndim > 0
        if vec_pos:
            # (b, S, 1, 1) one-hot: row b writes at its own slot pos[b] % S
            write_oh = (jnp.arange(s_slots)[None, :]
                        == slot[:, None])[:, :, None, None]

        def body(i, carry):
            # fori_loop + in-place dynamic_update_slice keeps the (donated)
            # cache stack aliased input->output — a lax.scan over ys would
            # allocate a second full cache (OVER-HBM at 32k depth; §Perf).
            h, ks, vs = carry
            bp = _tree_slice_dyn(params["blocks"], i)
            h = shard_activation(h, "act")
            hn = norm(h, bp, "ln1", cfg)
            from repro.models.attention import (decode_attention,
                                                merge_heads_out, project_qkv)

            positions = (pos[:, None].astype(jnp.int32) if vec_pos
                         else jnp.full((b, 1), pos, jnp.int32))
            q, k, v = project_qkv(hn, bp["attn"], cfg, positions,
                                  use_rope=cfg.family != "encdec")
            if vec_pos:
                k_cache = jax.lax.dynamic_index_in_dim(ks, i, 0,
                                                       keepdims=False)
                v_cache = jax.lax.dynamic_index_in_dim(vs, i, 0,
                                                       keepdims=False)
                k_cache = jnp.where(write_oh, k.astype(ks.dtype), k_cache)
                v_cache = jnp.where(write_oh, v.astype(vs.dtype), v_cache)
                ks = jax.lax.dynamic_update_index_in_dim(ks, k_cache, i, 0)
                vs = jax.lax.dynamic_update_index_in_dim(vs, v_cache, i, 0)
            else:
                ks = jax.lax.dynamic_update_slice(
                    ks, k.astype(ks.dtype).reshape(1, b, 1, *k.shape[2:]),
                    (i, 0, slot, 0, 0))
                vs = jax.lax.dynamic_update_slice(
                    vs, v.astype(vs.dtype).reshape(1, b, 1, *v.shape[2:]),
                    (i, 0, slot, 0, 0))
                k_cache = jax.lax.dynamic_index_in_dim(ks, i, 0,
                                                       keepdims=False)
                v_cache = jax.lax.dynamic_index_in_dim(vs, i, 0,
                                                       keepdims=False)
            o = decode_attention(q, k_cache, v_cache, n_valid)
            h = h + merge_heads_out(o, bp["attn"])
            hn = norm(h, bp, "ln2", cfg)
            if is_moe:
                ff, _ = moe_block(hn, bp["moe"], cfg)
            else:
                ff = mlp(hn, bp["mlp"], cfg)
            return h + ff, ks, vs

        if cfg.unroll_loops:   # cost-reference compiles (core.costref)
            carry = (x, ks0, vs0)
            for i in range(n_layers):
                carry = body(jnp.asarray(i), carry)
            x, ks, vs = carry
        else:
            x, ks, vs = jax.lax.fori_loop(0, n_layers, body, (x, ks0, vs0))
        new_cache["blocks"] = {"k": ks, "v": vs}

    x = norm(x, params, "final_norm", cfg)
    logits = lm_logits(x[:, -1], params, cfg)
    return logits, new_cache
