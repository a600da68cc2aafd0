"""Seeded weights, made by the benchmark and not by the program, so that
the plain reference can make the very same values again from the seed
without taking anything the program holds.

The layout is the configuration's family's (``family.py``): the program's
parameter tree, which the harness checks against the program's own
abstract parameters before it hands the weights over.  A leaf takes the
served dtype or float32 as its family says, and may be stacked over a
count of layers on a leading axis.

Every leaf, and every entry of a stacked leaf, has its own key folded from
the seed, the leaf's dotted name and its index in the stack, so one layer
can be made alone (the reference's layer-by-layer pass) and equals that
layer of the whole tree (one jitted call).
"""
from __future__ import annotations

import functools
import json
import math
import zlib
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

import family


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
def _make_fn(fam, cj: str, dtype_name: str):
    c = json.loads(cj)
    dt = jnp.dtype(dtype_name)

    def make(key):
        flat = {}
        for name, (shape, served, init, stack) in fam.layout(c).items():
            k = _leaf_key(key, name)
            ldt = dt if served else jnp.float32
            if stack:
                flat[name] = jax.vmap(
                    lambda i: _value(jax.random.fold_in(k, i), shape, ldt,
                                     init))(jnp.arange(stack))
            else:
                flat[name] = _value(k, shape, ldt, init)
        return _nest(flat)

    return jax.jit(make)


def make(c: Dict, seed: int, dtype) -> Dict:
    """The whole parameter tree, in one jitted call on the default device."""
    return _make_fn(family.of(c), _frozen(c),
                    jnp.dtype(dtype).name)(base_key(seed))


@functools.lru_cache(maxsize=None)
def _layer_fn(fam, cj: str, dtype_name: str, names: tuple):
    c = json.loads(cj)
    dt = jnp.dtype(dtype_name)
    leaves = fam.layout(c)

    def one(key, index):
        out = {}
        for j, (local, name) in enumerate(names):
            shape, served, init, stack = leaves[name]
            k = _leaf_key(key, name)
            if stack:
                k = jax.random.fold_in(k, index[j])
            v = _value(k, shape, dt if served else jnp.float32, init)
            out[local] = v.astype(jnp.float32)
        return out

    return jax.jit(one)


def layer(c: Dict, seed: int, i: int, dtype) -> Dict[str, jax.Array]:
    """Decoder layer ``i``'s leaves as float32, named as the family's
    ``layer_leaves`` names them."""
    fam = family.of(c)
    parts = fam.layer_leaves(c, i)
    names = tuple((local, name) for local, (name, _) in parts.items())
    index = jnp.asarray([-1 if j is None else j for _, j in parts.values()],
                        jnp.int32)
    return _layer_fn(fam, _frozen(c), jnp.dtype(dtype).name, names)(
        base_key(seed), index)


@functools.lru_cache(maxsize=None)
def _top_fn(fam, cj: str, dtype_name: str, name: str):
    shape, served, init, _ = fam.layout(json.loads(cj))[name]
    dt = jnp.dtype(dtype_name) if served else jnp.float32
    return jax.jit(lambda key: _value(_leaf_key(key, name), shape, dt, init))


def top(c: Dict, seed: int, name: str, dtype) -> jax.Array:
    """One unstacked leaf (``embed.tok``, ``final_norm``, ``lm_head``) in
    its stored dtype."""
    return _top_fn(family.of(c), _frozen(c), jnp.dtype(dtype).name,
                   name)(base_key(seed))


def check_layout(c: Dict, dtype, abstract) -> None:
    """Raise unless the program's abstract parameter tree has exactly this
    layout's names, shapes and dtypes."""
    dt = jnp.dtype(dtype)
    want = {}
    for name, (shape, served, _, stack) in family.of(c).layout(c).items():
        want[name] = ((stack,) + shape if stack else shape,
                      dt if served else jnp.dtype(jnp.float32))
    got = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(abstract)[0]:
        name = ".".join(str(getattr(p, "key", p)) for p in path)
        got[name] = (tuple(leaf.shape), jnp.dtype(leaf.dtype))
    if got != want:
        raise ValueError(f"program parameter layout differs from the "
                         f"benchmark's: only program {set(got) - set(want)}, "
                         f"only benchmark {set(want) - set(got)}, "
                         f"differing {[k for k in got if k in want and got[k] != want[k]]}")
