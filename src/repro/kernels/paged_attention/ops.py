"""Jitted public wrappers for the paged-attention decode kernel.

``paged_attention_decode`` dispatches between the Pallas kernel and the
pure-jnp gather reference by a static ``impl`` flag:

* ``"kernel"`` — the Pallas kernel (``interpret=True`` off-TPU so CPU
  CI exercises the real code path);
* ``"ref"`` — the XLA-compiled gather oracle (fast on CPU, where the
  Pallas interpreter would dominate wall-clock);
* ``"auto"`` — kernel on TPU backends, ref elsewhere.

Both impls share one contract (see the kernel docstring): the batched
executor and benchmarks call this wrapper only.
"""
from __future__ import annotations

import functools

import jax

from repro.kernels.paged_attention.paged_attention import paged_attention
from repro.kernels.paged_attention.ref import paged_attention_ref

__all__ = ["paged_attention_decode", "resolve_impl"]


def resolve_impl(impl: str = "auto") -> str:
    """Resolve "auto" to "kernel" (TPU) or "ref" (anything else)."""
    if impl != "auto":
        return impl
    return "kernel" if jax.default_backend() == "tpu" else "ref"


@functools.partial(jax.jit, static_argnames=("window", "impl", "interpret"))
def paged_attention_decode(q, k, v, k_pages, v_pages, layer, block_tables,
                           lengths, *, window: int = 0, impl: str = "auto",
                           interpret: bool = False):
    """One decode step of paged attention, writing the step's K/V into
    the pool first; see the kernel docstring.

    q: (b, hq, d); k/v: (b, hkv, d); k_pages/v_pages:
    (n_layers, hkv, n_pages, d, block_tokens); layer: int32 scalar;
    block_tables: (b, nb) int32; lengths: (b,) int32.  Returns
    (out (b, hq, d), k_pages, v_pages).
    """
    impl = resolve_impl(impl)
    if impl == "kernel":
        return paged_attention(q, k, v, k_pages, v_pages, layer,
                               block_tables, lengths, window=window,
                               interpret=interpret)
    if impl == "ref":
        return paged_attention_ref(q, k, v, k_pages, v_pages, layer,
                                   block_tables, lengths, window=window)
    raise ValueError(f"unknown paged-attention impl: {impl!r}")
