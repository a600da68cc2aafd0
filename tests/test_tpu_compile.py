"""Compile the serving path's Pallas kernel for a described TPU v5e chip.

Interpret mode (every other kernel test) cannot show tiling or VMEM
refusals; the TPU compiler, given a chip that is described and not
attached, can.  Nothing runs: these tests pass shapes and read the
compiled HLO for the kernel (``tpu_custom_call``).  All of them live in
this one file, so under several pytest workers only the worker that is
given it loads the TPU library.
"""
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
    n_pages = W * NB + 1
    args = (_spec((W, cfg.num_heads, d), jnp.bfloat16, one_chip),
            _spec((hkv, n_pages, DEFAULT_BLOCK_TOKENS, d), jnp.bfloat16,
                  one_chip),
            _spec((hkv, n_pages, DEFAULT_BLOCK_TOKENS, d), jnp.bfloat16,
                  one_chip),
            _spec((W, NB), jnp.int32, one_chip),
            _spec((W,), jnp.int32, one_chip))
    hlo = paged_attention_decode.lower(
        *args, window=window, impl="kernel",
        interpret=False).compile().as_text()
    assert "tpu_custom_call" in hlo


def test_full_width_paged_decode_step_compiles(one_chip, no_persistent_cache):
    cfg = get_config("smollm-135m")
    params = jax.tree.map(lambda s: _spec(s.shape, s.dtype, one_chip),
                          abstract_params(cfg))
    pages = transformer.paged_kv_shape(cfg, W * NB + 1, DEFAULT_BLOCK_TOKENS)
    args = (params,
            _spec((W,), jnp.int32, one_chip),
            _spec((W,), jnp.int32, one_chip),
            _spec(pages, cfg.compute_dtype, one_chip),
            _spec(pages, cfg.compute_dtype, one_chip),
            _spec((W, NB), jnp.int32, one_chip))
    step = jax.jit(model.paged_decode_fn(cfg, attn_impl="kernel"),
                   donate_argnums=(3, 4))
    hlo = step.lower(*args).compile().as_text()
    assert "tpu_custom_call" in hlo
