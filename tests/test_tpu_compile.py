"""Compile the serving path's Pallas kernel for a described TPU v5e chip.

Interpret mode (every other kernel test) cannot show tiling or VMEM
refusals; the TPU compiler, given a chip that is described and not
attached, can.  Nothing runs: these tests pass shapes and read the
compiled HLO for the kernel (``tpu_custom_call``).  All of them live in
this one file, so under several pytest workers only the worker that is
given it loads the TPU library.
"""
import math
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.paged_attention.ops import (DEFAULT_BLOCK_TOKENS,
                                               paged_attention_decode)
from repro.models import model, transformer
from repro.models.init import abstract_params

W = 8           # batch width of the serving executor
NB = 4          # pages per block-table row


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means: no TPU compiler
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without the chip; keep it out."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)
        cc.reset_cache()


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("window", [0, 64])
def test_paged_kernel_compiles_at_smollm_widths(one_chip, no_persistent_cache,
                                                 window):
    cfg = get_config("smollm-135m")
    hkv, d = cfg.num_kv_heads, cfg.head_dim
    pages = transformer.paged_kv_shape(cfg, W * NB + 1, DEFAULT_BLOCK_TOKENS)
    args = (_spec((W, cfg.num_heads, d), jnp.bfloat16, one_chip),
            _spec((W, hkv, d), jnp.bfloat16, one_chip),
            _spec((W, hkv, d), jnp.bfloat16, one_chip),
            _spec(pages, jnp.bfloat16, one_chip),
            _spec(pages, jnp.bfloat16, one_chip),
            _spec((), jnp.int32, one_chip),
            _spec((W, NB), jnp.int32, one_chip),
            _spec((W,), jnp.int32, one_chip))
    hlo = paged_attention_decode.lower(
        *args, window=window, impl="kernel",
        interpret=False).compile().as_text()
    assert "tpu_custom_call" in hlo


def _paged_step(cfg, one_chip, width, pages_per_row):
    """The serving executor's decode program, compiled for the chip at
    ``width`` rows of ``pages_per_row`` pages, pools donated."""
    params = jax.tree.map(lambda s: _spec(s.shape, s.dtype, one_chip),
                          abstract_params(cfg))
    pages = transformer.paged_kv_shape(cfg, width * pages_per_row + 1,
                                       DEFAULT_BLOCK_TOKENS)
    args = (params,
            _spec((width,), jnp.int32, one_chip),
            _spec((width,), jnp.int32, one_chip),
            _spec(pages, cfg.compute_dtype, one_chip),
            _spec(pages, cfg.compute_dtype, one_chip),
            _spec((width, pages_per_row), jnp.int32, one_chip))
    step = jax.jit(model.paged_decode_fn(cfg, attn_impl="kernel"),
                   donate_argnums=(3, 4))
    return pages, step.lower(*args).compile().as_text()


def test_full_width_paged_decode_step_compiles(one_chip, no_persistent_cache):
    _, hlo = _paged_step(get_config("smollm-135m"), one_chip, W, NB)
    assert "tpu_custom_call" in hlo


# ops that only name or pass on an array, never move its bytes
_PASS_THROUGH = {"parameter", "get-tuple-element", "tuple", "bitcast",
                 "while", "custom-call"}


def test_decode_step_leaves_the_kv_pool_in_place(one_chip,
                                                 no_persistent_cache):
    """At the benchmark cell's shapes (128 rows of 16 pages, 2049 pages)
    no XLA op copies, slices, scatters into or relayouts the stacked KV
    pool or one layer of it: only the paged-attention kernel touches the
    pool, which it reads and writes in place, and both pools alias the
    donated inputs."""
    cfg = get_config("smollm-135m")
    pages, hlo = _paged_step(cfg, one_chip, 128, 16)
    pool = math.prod(pages)
    layer = pool // pages[0]
    moved = []
    params = {}                 # the entry computation's, by number
    entry = hlo.index("\nENTRY ")
    for line in hlo.splitlines():
        m = re.match(r"\s*(?:ROOT )?%(\S+) = (\w+)\[([\d,]*)\]\S* "
                     r"([\w-]+)\((.*)", line)
        if not m:
            continue
        name, dims, op = m.group(1), m.group(3), m.group(4)
        size = math.prod(int(x) for x in dims.split(",") if x)
        if op == "parameter" and hlo.index(line) > entry:
            params[int(re.match(r"(\d+)\)", m.group(5)).group(1))] = size
        if size in (pool, layer) and (
                op not in _PASS_THROUGH
                or (op == "custom-call" and "tpu_custom_call" not in line)):
            moved.append(f"{op} {name}")
    assert not moved, moved
    aliases = re.search(r"input_output_alias=\{ (.*?) \}", hlo)
    assert aliases, "the pools alias no input"
    pairs = dict(re.findall(r"\{(\d+)\}: \((\d+), \{\}", aliases.group(1)))
    assert set(pairs) == {"1", "2"}, pairs
    assert all(params[int(p)] == pool for p in pairs.values()), pairs
