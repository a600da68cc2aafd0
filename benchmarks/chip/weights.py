"""Seeded weights of a dense decoder, made by the benchmark and not by the
program, so that the plain reference can make the very same values again
from the seed without taking anything the program holds.

The layout is the program's dense-decoder parameter tree (``embed.tok``,
``blocks.*`` stacked over layers, ``final_norm``, ``lm_head`` when the head
is untied); the harness checks it against the program's own abstract
parameters before it hands the weights over.  Matrices take the served
dtype, vectors stay float32, as the program keeps them.

Every leaf, and every layer of a stacked leaf, has its own key folded from
the seed, so one layer can be made alone (the reference's layer-by-layer
pass) and equals that layer of the whole tree (one jitted call).
"""
from __future__ import annotations

import functools
import json
import math
import zlib
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

# name -> (shape of one layer, stored in the served dtype, init, stacked)
Leaf = Tuple[Tuple[int, ...], bool, str, bool]


def layout(c: Dict) -> Dict[str, Leaf]:
    """Flat ``{dotted.name: (shape, served_dtype, init, stacked)}``.  As in
    the program, a leaf of two or more dimensions as stored (a matrix, or
    a vector stacked over layers) takes the served dtype."""
    d, ff, V = c["d_model"], c["d_ff"], c["vocab_size"]
    q = c["num_heads"] * c["head_dim"]
    kv = c["num_kv_heads"] * c["head_dim"]
    leaves = [
        ("embed.tok", (V, d), "embed", False),
        ("final_norm", (d,), "norm", False),
        ("blocks.ln1", (d,), "norm", True),
        ("blocks.ln2", (d,), "norm", True),
        ("blocks.attn.wq", (d, q), "fan_in", True),
        ("blocks.attn.wk", (d, kv), "fan_in", True),
        ("blocks.attn.wv", (d, kv), "fan_in", True),
        ("blocks.attn.wo", (q, d), "fan_in", True),
        ("blocks.mlp.wi", (d, ff), "fan_in", True),
        ("blocks.mlp.wg", (d, ff), "fan_in", True),
        ("blocks.mlp.wo", (ff, d), "fan_in", True),
    ]
    if c.get("qkv_bias"):
        leaves += [("blocks.attn.bq", (q,), "bias", True),
                   ("blocks.attn.bk", (kv,), "bias", True),
                   ("blocks.attn.bv", (kv,), "bias", True)]
    if not c.get("tie_embeddings"):
        leaves.append(("lm_head", (d, V), "fan_in", False))
    return {name: (shape, stacked or len(shape) >= 2, init, stacked)
            for name, shape, init, stacked in leaves}


def base_key(seed: int) -> jax.Array:
    """A key from any whole-number seed (wider than 32 bits too)."""
    w = np.random.SeedSequence(int(seed)).generate_state(2)
    return jax.random.fold_in(jax.random.key(int(w[0])), int(w[1]))


def _leaf_key(key, name: str):
    return jax.random.fold_in(key, zlib.crc32(name.encode()) & 0x7FFFFFFF)


def _value(key, shape, dtype, init):
    z = jax.random.normal(key, shape, jnp.float32)
    if init == "norm":
        return (1.0 + 0.1 * z).astype(dtype)
    if init == "bias":
        return (0.1 * z).astype(dtype)
    if init == "embed":
        return (0.02 * z).astype(dtype)
    return (z / math.sqrt(shape[-2])).astype(dtype)


def _nest(flat: Dict[str, jax.Array]) -> Dict:
    tree: Dict = {}
    for name, v in flat.items():
        node = tree
        *path, last = name.split(".")
        for p in path:
            node = node.setdefault(p, {})
        node[last] = v
    return tree


def _frozen(c: Dict) -> str:
    return json.dumps(c, sort_keys=True)


@functools.lru_cache(maxsize=None)
def _make_fn(cj: str, dtype_name: str):
    c = json.loads(cj)
    dt = jnp.dtype(dtype_name)
    L = c["num_layers"]

    def make(key):
        flat = {}
        for name, (shape, is_mat, init, stacked) in layout(c).items():
            k = _leaf_key(key, name)
            ldt = dt if is_mat else jnp.float32
            if stacked:
                flat[name] = jax.vmap(
                    lambda i: _value(jax.random.fold_in(k, i), shape, ldt,
                                     init))(jnp.arange(L))
            else:
                flat[name] = _value(k, shape, ldt, init)
        return _nest(flat)

    return jax.jit(make)


def make(c: Dict, seed: int, dtype) -> Dict:
    """The whole parameter tree, in one jitted call on the default device."""
    return _make_fn(_frozen(c), jnp.dtype(dtype).name)(base_key(seed))


@functools.lru_cache(maxsize=None)
def _layer_fn(cj: str, dtype_name: str):
    c = json.loads(cj)
    dt = jnp.dtype(dtype_name)

    def one(key, i):
        out = {}
        for name, (shape, is_mat, init, stacked) in layout(c).items():
            if not stacked:
                continue
            k = jax.random.fold_in(_leaf_key(key, name), i)
            v = _value(k, shape, dt if is_mat else jnp.float32, init)
            out[name[len("blocks."):]] = v.astype(jnp.float32)
        return out

    return jax.jit(one)


def layer(c: Dict, seed: int, i: int, dtype) -> Dict[str, jax.Array]:
    """Layer ``i``'s leaves as float32, named without ``blocks.``."""
    return _layer_fn(_frozen(c), jnp.dtype(dtype).name)(
        base_key(seed), jnp.int32(i))


@functools.lru_cache(maxsize=None)
def _top_fn(cj: str, dtype_name: str, name: str):
    c = json.loads(cj)
    shape, is_mat, init, _ = layout(c)[name]
    dt = jnp.dtype(dtype_name) if is_mat else jnp.float32
    return jax.jit(lambda key: _value(_leaf_key(key, name), shape, dt, init))


def top(c: Dict, seed: int, name: str, dtype) -> jax.Array:
    """One unstacked leaf (``embed.tok``, ``final_norm``, ``lm_head``) in
    its stored dtype."""
    return _top_fn(_frozen(c), jnp.dtype(dtype).name, name)(base_key(seed))


def check_layout(c: Dict, dtype, abstract) -> None:
    """Raise unless the program's abstract parameter tree has exactly this
    layout's names, shapes and dtypes."""
    dt = jnp.dtype(dtype)
    want = {}
    L = c["num_layers"]
    for name, (shape, is_mat, _, stacked) in layout(c).items():
        want[name] = ((L,) + shape if stacked else shape,
                      dt if is_mat else jnp.dtype(jnp.float32))
    got = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(abstract)[0]:
        name = ".".join(str(getattr(p, "key", p)) for p in path)
        got[name] = (tuple(leaf.shape), jnp.dtype(leaf.dtype))
    if got != want:
        raise ValueError(f"program parameter layout differs from the "
                         f"benchmark's: only program {set(got) - set(want)}, "
                         f"only benchmark {set(want) - set(got)}, "
                         f"differing {[k for k in got if k in want and got[k] != want[k]]}")
