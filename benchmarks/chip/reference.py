"""The plain reference: a float32 dense decoder in straightforward
``jax.numpy``, shared by every dense configuration, with no kernel, cache,
paging or batching of requests.

RMSNorm, rotary position embedding (half-split rotation, as HF Llama and
Qwen2), grouped-query attention with a causal mask and a full softmax,
optional biases on Q/K/V, a SwiGLU MLP, and a tied or untied head.  Every
matrix product runs at ``Precision.HIGHEST``.  It imports nothing of the
program: the weights come from ``weights`` and the seed, and the sizes
from the configuration's file.

It runs layer by layer (each layer's weights are made, used and dropped)
over blocks of rows, so that it fits next to nothing else on one chip.

``quant="fp8"`` is the control: every operand of every matrix product is
rounded to float8 e4m3 with a scale per row (activations) or per output
column (weights), the step below the bfloat16 the configuration serves in.
"""
from __future__ import annotations

import functools
import json
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

import weights as W

HI = jax.lax.Precision.HIGHEST
F8_MAX = 448.0


def _q8(x, axis):
    """Round to float8 e4m3 with a scale along ``axis`` (fp8 control)."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / F8_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(x, w, quant):
    if quant:
        x, w = _q8(x, -1), _q8(w, 0)
    return jnp.einsum("...d,df->...f", x, w, precision=HI)


def _rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, pos, theta):
    """x: (b, s, h, hd); pos: (s,)."""
    half = x.shape[-1] // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = pos[:, None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


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


@functools.lru_cache(maxsize=None)
def _block_fn(cj: str, quant: bool):
    c = json.loads(cj)
    return jax.jit(lambda x, w: block(x, w, c, quant))


def _head_w(c: Dict, seed: int, dtype) -> jax.Array:
    if c.get("tie_embeddings"):
        return W.top(c, seed, "embed.tok", dtype).astype(jnp.float32).T
    return W.top(c, seed, "lm_head", dtype).astype(jnp.float32)


def hidden(c: Dict, seed: int, dtype, tokens: np.ndarray,
           quants: Sequence[bool], rows: int) -> List[np.ndarray]:
    """Final-norm hidden states (n, s, d) of the token rows, once for each
    entry of ``quants`` (False: the float32 reference; True: the fp8
    control), layer by layer over blocks of ``rows`` rows."""
    cj = W._frozen(c)
    n, s = tokens.shape
    pad = -n % rows
    toks = np.concatenate([tokens, np.zeros((pad, s), tokens.dtype)])
    emb = W.top(c, seed, "embed.tok", dtype)
    x0 = jnp.take(emb, jnp.asarray(toks), axis=0).astype(jnp.float32)
    del emb
    xs = [x0 for _ in quants]
    for i in range(c["num_layers"]):
        w = W.layer(c, seed, i, dtype)
        for j, quant in enumerate(quants):
            f = _block_fn(cj, bool(quant))
            xs[j] = jnp.concatenate(
                [f(xs[j][r:r + rows], w) for r in range(0, n + pad, rows)])
        del w
    fn = W.top(c, seed, "final_norm", dtype)
    return [np.asarray(_rmsnorm(x, fn, c["norm_eps"]))[:n] for x in xs]


@functools.lru_cache(maxsize=None)
def _gap_fn(quant_present: bool):
    def f(h_ref, h_ctl, w, served):
        lr = jnp.einsum("pd,dv->pv", h_ref, w, precision=HI)
        best = jnp.max(lr, -1)
        g_served = best - jnp.take_along_axis(lr, served[:, None], 1)[:, 0]
        if not quant_present:
            return g_served, g_served
        lq = _mm(h_ctl, w, True)
        pick = jnp.argmax(lq, -1)
        g_ctl = best - jnp.take_along_axis(lr, pick[:, None], 1)[:, 0]
        return g_served, g_ctl
    return jax.jit(f)


def served_gaps(c: Dict, seed: int, dtype,
                seqs: Sequence[Tuple[np.ndarray, np.ndarray]],
                pad_to: int, rows: int, control: bool,
                chunk: int = 256) -> Tuple[np.ndarray, Optional[np.ndarray]]:
    """For served requests ``(prompt, served_tokens)``: at every position
    where a token was served, the gap by which the served token's
    reference logit lies below the reference's best; with ``control``,
    also the gap of the token the fp8 control puts first there."""
    toks = np.zeros((len(seqs), pad_to), np.int32)
    where, served = [], []
    for r, (prompt, out) in enumerate(seqs):
        full = np.concatenate([prompt, out[:-1]]).astype(np.int32)
        toks[r, :len(full)] = full
        p0 = len(prompt) - 1
        for j, t in enumerate(out):
            where.append((r, p0 + j))
            served.append(int(t))
    quants = [False, True] if control else [False]
    hs = hidden(c, seed, dtype, toks, quants, rows)
    idx = tuple(np.asarray(where).T)
    h_ref = hs[0][idx]
    h_ctl = hs[1][idx] if control else h_ref
    w = _head_w(c, seed, dtype)
    served_a = np.asarray(served, np.int32)
    f = _gap_fn(control)
    gs, gc = [], []
    for a in range(0, len(served_a), chunk):
        n = min(chunk, len(served_a) - a)
        padn = chunk - n
        sl = slice(a, a + n)

        def padded(x):
            return np.concatenate([x[sl], np.zeros((padn,) + x.shape[1:],
                                                   x.dtype)])
        g1, g2 = f(padded(h_ref), padded(h_ctl), w, padded(served_a))
        gs.append(np.asarray(g1)[:n])
        gc.append(np.asarray(g2)[:n])
    return np.concatenate(gs), (np.concatenate(gc) if control else None)
