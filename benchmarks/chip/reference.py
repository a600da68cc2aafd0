"""The plain reference: a float32 decoder in straightforward ``jax.numpy``,
with no kernel, cache, paging or batching of requests.  What is shared by
every family is here: the matrix product, RMSNorm, rotary position
embedding, the layer-by-layer pass and the served tokens' logit gaps.  Each
family's decoder layer is its ``block`` (``family.py``).

Every matrix product runs at ``Precision.HIGHEST``.  It imports nothing of
the program: the weights come from ``weights`` and the seed, and the sizes
from the configuration's file.

It runs layer by layer (each layer's weights are made, used and dropped)
over blocks of rows, so that it fits next to nothing else on one chip.

``quant`` is the control: every operand of every matrix product is rounded
to float8 e4m3 with a scale per row (activations) or per output column
(weights), the step below the bfloat16 the configuration serves in.
"""
from __future__ import annotations

import functools
import json
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

import family
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


@functools.lru_cache(maxsize=None)
def _block_fn(fam, cj: str, quant: bool):
    c = json.loads(cj)
    return jax.jit(lambda x, w: fam.block(x, w, c, quant))


def _head_w(c: Dict, seed: int, dtype) -> jax.Array:
    if c.get("tie_embeddings"):
        return W.top(c, seed, "embed.tok", dtype).astype(jnp.float32).T
    return W.top(c, seed, "lm_head", dtype).astype(jnp.float32)


def hidden(c: Dict, seed: int, dtype, tokens: np.ndarray,
           quants: Sequence[bool], rows: int) -> List[np.ndarray]:
    """Final-norm hidden states (n, s, d) of the token rows, once for each
    entry of ``quants`` (False: the float32 reference; True: the fp8
    control), layer by layer over blocks of ``rows`` rows."""
    fam, cj = family.of(c), W._frozen(c)
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
            f = _block_fn(fam, cj, bool(quant))
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
