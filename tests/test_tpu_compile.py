"""Compile the serving path's Pallas kernel for a described TPU v5e chip.

Interpret mode (every other kernel test) cannot show tiling or VMEM
refusals; the TPU compiler, given a chip that is described and not
attached, can.  Nothing runs: these tests pass shapes and read the
compiled HLO for the kernel (``tpu_custom_call``).  All of them live in
this one file, so under several pytest workers only the worker that is
given it loads the TPU library.
"""
import dataclasses
import math
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.paged_attention.ops import paged_attention_decode
from repro.models import model, transformer
from repro.models.init import abstract_params
from repro.serve.kv_cache import BLOCK_TOKENS

W = 8           # batch width of the serving executor
NB = 4          # pages per block-table row
VMEM_SCOPED = 16 * 2**20    # v5e's default scoped VMEM a kernel may use


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
    pages = transformer.paged_kv_shape(cfg, W * NB + 1, BLOCK_TOKENS)
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
                                       BLOCK_TOKENS)
    args = (params,
            _spec((width,), jnp.int32, one_chip),
            _spec((width,), jnp.int32, one_chip),
            _spec(pages, cfg.compute_dtype, one_chip),
            _spec(pages, cfg.compute_dtype, one_chip),
            _spec((width, pages_per_row), jnp.int32, one_chip))
    step = jax.jit(model.paged_decode_fn(cfg, attn_impl="kernel"),
                   donate_argnums=(3, 4))
    return pages, step.lower(*args).compile()


def test_full_width_paged_decode_step_compiles(one_chip, no_persistent_cache):
    _, compiled = _paged_step(get_config("smollm-135m"), one_chip, W, NB)
    assert "tpu_custom_call" in compiled.as_text()


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
    pages, compiled = _paged_step(cfg, one_chip, 128, 16)
    hlo = compiled.as_text()
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


# The benchmark's qwen2.5-14b-8layer: Qwen2.5-14B at its published widths
# (40 query and 8 KV heads of 128, a group of 5; QKV biases; untied head),
# cut to 8 of its 48 layers, served in bf16 at 32 rows of 16 pages.
QWEN_CUT = dict(num_layers=8, param_dtype=jnp.bfloat16)
HBM = 16 * 10**9            # one v5e chip


def _resident(compiled):
    """Bytes a compiled program holds on the device while it runs: its
    arguments, what it writes that is not written in place, and its
    temporaries."""
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            - m.alias_size_in_bytes + m.temp_size_in_bytes)


def _nbytes(tree):
    return sum(math.prod(x.shape) * jnp.dtype(x.dtype).itemsize
               for x in jax.tree.leaves(tree))


def test_qwen_cut_decode_step_compiles_at_group_5_and_fits(
        one_chip, no_persistent_cache):
    cfg = dataclasses.replace(get_config("qwen2.5-14b"), **QWEN_CUT)
    assert (cfg.num_heads // cfg.num_kv_heads, cfg.head_dim) == (5, 128)
    pages, compiled = _paged_step(cfg, one_chip, 32, 16)
    assert "tpu_custom_call" in compiled.as_text()
    weights = _nbytes(abstract_params(cfg))
    pools = 2 * math.prod(pages) * 2        # K and V, bf16
    assert weights == pytest.approx(7.52e9, rel=1e-3)
    assert pools == pytest.approx(2.15e9, rel=1e-2)
    # the weights and both pools are arguments; the pools are donated
    assert weights + pools <= compiled.memory_analysis(
        ).argument_size_in_bytes
    assert _resident(compiled) < HBM


def test_qwen_cut_longest_prefill_and_page_scatter_fit(one_chip,
                                                        no_persistent_cache):
    """The cell's longest prompt (1500 tokens, a cache of 2012 positions)
    through the batch-1 prefill and the page scatter, each beside what
    stays on the device while it runs: the pool beside the prefill, the
    weights beside the scatter."""
    cfg = dataclasses.replace(get_config("qwen2.5-14b"), **QWEN_CUT)
    params = jax.tree.map(lambda s: _spec(s.shape, s.dtype, one_chip),
                          abstract_params(cfg))
    tokens = {"tokens": _spec((1, 1500), jnp.int32, one_chip)}
    pages = transformer.paged_kv_shape(cfg, 32 * 16 + 1,
                                       BLOCK_TOKENS)

    def prefill(p, b):
        return transformer.prefill(p, b, cfg, max_len=2012)

    pre = jax.jit(prefill).lower(params, tokens).compile()
    cache = jax.tree.map(lambda s: _spec(s.shape, s.dtype, one_chip),
                         jax.eval_shape(prefill, params, tokens)[1])

    def scatter(c, kp, vp, pg, off):
        return transformer.scatter_prefill_pages(c, cfg, kp, vp, pg, off)

    pos = _spec((1500,), jnp.int32, one_chip)
    sc = jax.jit(scatter, donate_argnums=(1, 2)).lower(
        cache, _spec(pages, jnp.bfloat16, one_chip),
        _spec(pages, jnp.bfloat16, one_chip), pos, pos).compile()
    pools = 2 * math.prod(pages) * 2
    assert _resident(pre) + pools < HBM
    assert _resident(sc) + _nbytes(params) < HBM


def _kernel_vmem(hlo):
    """VMEM bytes the paged-attention custom call holds, from the scoped
    memory configs of its backend config (memory space 1 is VMEM)."""
    line, = [ln for ln in hlo.splitlines()
             if "tpu_custom_call" in ln and "paged_attention" in ln]
    used = re.search(r'"used_scoped_memory_configs":\[(.*?)\]', line)
    return sum(int(size) for space, size in re.findall(
        r'"memory_space":"(\d+)","offset":"\d+","size":"(\d+)"',
        used.group(1)) if space == "1")


@pytest.mark.parametrize("arch,cut,width", [
    ("smollm-135m", {}, 128),               # group 3, head_dim 64
    ("qwen2.5-14b", QWEN_CUT, 32),          # group 5, head_dim 128
])
def test_paged_kernel_scratch_fits_vmem(one_chip, no_persistent_cache,
                                        arch, cut, width):
    """At the benchmark cells' widths (16 pages a row) the decode step
    holds the kernel, whose VMEM holds two slots of one page of every kv
    head for K and for V, within the scoped VMEM of a v5e."""
    cfg = dataclasses.replace(get_config(arch), **cut)
    _, compiled = _paged_step(cfg, one_chip, width, 16)
    vmem = _kernel_vmem(compiled.as_text())
    slots = 2 * 2 * cfg.num_kv_heads * cfg.head_dim * BLOCK_TOKENS \
        * jnp.dtype(cfg.compute_dtype).itemsize
    assert slots <= vmem <= VMEM_SCOPED, (slots, vmem)
