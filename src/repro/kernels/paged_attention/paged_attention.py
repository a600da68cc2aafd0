"""Paged-attention decode TPU kernel (pl.pallas_call + scalar-prefetch
block tables): one decode step for a batch of live slots whose KV lives
in the :class:`repro.serve.kv_cache.PagedKVCache` allocator's block
tables instead of a dense per-slot cache.  The step's new K/V row is
written into its page in place, inside the same kernel.

Block-table ABI (shared with ``PagedKVCache``)
----------------------------------------------
The serving KV cache is one pool of fixed-size *pages* per K and V,
stacked over every layer, each page a ``(head_dim, block_tokens)`` tile
with the tokens in the lanes:

    k_pages, v_pages : (n_layers, hkv, n_pages, head_dim, block_tokens)

A slot's tokens occupy the pages named by its *block table* row, in
order: absolute position ``p`` of slot ``b`` lives in page
``block_tables[b, p // block_tokens]`` at in-page column
``p % block_tokens``, in every layer.  ``lengths[b]`` is the number of
valid positions (attention span) for slot ``b``, the step's new token
included: its K/V goes to position ``lengths[b] - 1``.  Rows past their
table's populated prefix may point anywhere (conventionally a null
page) — they are never read because the length mask excludes them.
``lengths[b] == 0`` marks an *inactive* batch row: the kernel skips
every page, writes nothing into the pool and outputs zeros, which is
what lets a fixed-width batched executor mask empty rows instead of
recompiling at a new width.

``block_tokens`` is read off the page pool's shape and **is** the
kernel's kv tile: each grid step DMAs exactly one
``(head_dim, block_tokens)`` page into VMEM, so allocator blocks map
1:1 onto kernel ``block_k`` grid iterations with no partial-tile waste,
and the tile is lane-dense at every head_dim.  The allocator's default
(``FLASH_ATTENTION_BLOCK_K`` = 128, the Pallas flash-attention kv tile)
keeps both kernels fed whole MXU-aligned tiles; a pin test holds the two
constants equal.

TPU adaptation notes: the page gather is a *data-dependent* BlockSpec —
``pltpu.PrefetchScalarGridSpec`` prefetches the block table, the length
vector and the layer index into SMEM so the k/v index maps can address
``k_pages[layer, ih, block_tables[ib, ik]]`` per grid step; the kv-page
loop is the innermost grid dimension (TPU grids iterate sequentially, so
the online-softmax running max/denominator live in VMEM scratch across
pages); pages wholly past ``lengths[ib]`` skip their FLOPs with
``pl.when`` but still run their grid step, keeping the grid static.  The
pools are also outputs aliased to their inputs and left in HBM: at the
page that holds position ``lengths[ib] - 1`` the kernel patches the new
column into a VMEM copy of the page, attends over the patched tile, and
DMAs it back over the pool's page; the DMA is waited for at the row's
last grid step, so it overlaps the row's remaining pages.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

NEG_INF = -1e30

# The kernel's kv tile == the serve allocator's default page size ==
# the flash-attention kernel's block_k (pinned against each other and
# against repro.serve.kv_cache.FLASH_ATTENTION_BLOCK_K by test).
DEFAULT_BLOCK_TOKENS = 128


def _kernel(bt_ref, len_ref, li_ref, q_ref, kn_ref, vn_ref, k_ref, v_ref,
            o_ref, ko_ref, vo_ref, m_scr, l_scr, acc_scr, kbuf, vbuf, sems,
            *, scale: float, block_tokens: int, window: int):
    ib = pl.program_id(0)
    ih = pl.program_id(1)
    ik = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    length = len_ref[ib]
    # the page that takes this step's new token (position length - 1)
    last = jnp.maximum(length - 1, 0) // block_tokens

    def page_writes():
        dst = (li_ref[0], ih, bt_ref[ib, last])
        return (pltpu.make_async_copy(kbuf, ko_ref.at[dst], sems.at[0]),
                pltpu.make_async_copy(vbuf, vo_ref.at[dst], sems.at[1]))

    def attend(k, v):
        """Online-softmax update over one (d, bt) page in pool dtype."""
        q = q_ref[0, 0].astype(jnp.float32) * scale          # (g, d)
        s = jax.lax.dot_general(q, k.astype(jnp.float32),
                                (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)  # (g, bt)

        kv_pos = ik * block_tokens + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1)
        mask = kv_pos < length
        if window > 0:
            # the (single) query sits at absolute position length - 1
            mask &= kv_pos > (length - 1 - window)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[...]                                   # (g, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)                                # (g, bt)
        alpha = jnp.exp(m_prev - m_new)                       # (g, 1)
        l_scr[...] = alpha * l_scr[...] + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p, v.astype(jnp.float32), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)               # (g, d)
        m_scr[...] = m_new

    # Pages at or past the valid span contribute nothing — skip their
    # FLOPs entirely.  (This also keeps zero-length rows from ever
    # touching the scratch or the pool, so inactive rows finish with
    # l == 0 and the epilogue emits exact zeros instead of a softmax over
    # masked junk.)
    @pl.when((ik * block_tokens < length) & (ik != last))
    def _accumulate():
        attend(k_ref[0, 0, 0], v_ref[0, 0, 0])

    @pl.when((length > 0) & (ik == last))
    def _write_and_accumulate():
        off = (length - 1) % block_tokens
        # (d, b) @ (b, bt) one-hot at (ib, off): row ib's new K/V, moved
        # into column off (exact: each output is one product with 1.0)
        shape = (kn_ref.shape[2], block_tokens)
        put = ((jax.lax.broadcasted_iota(jnp.int32, shape, 0) == ib)
               & (jax.lax.broadcasted_iota(jnp.int32, shape, 1) == off))
        new = jax.lax.broadcasted_iota(jnp.int32, kbuf.shape, 1) == off
        for buf, rows, page in ((kbuf, kn_ref, k_ref), (vbuf, vn_ref, v_ref)):
            col = jax.lax.dot_general(
                rows[ih].astype(jnp.float32), put.astype(jnp.float32),
                (((1,), (0,)), ((), ())),
                precision=jax.lax.Precision.HIGHEST,
                preferred_element_type=jnp.float32)
            buf[...] = jnp.where(new, col.astype(buf.dtype), page[0, 0, 0])
        for copy in page_writes():
            copy.start()
        attend(kbuf[...], vbuf[...])

    @pl.when(ik == nk - 1)
    def _finish():
        @pl.when(length > 0)
        def _wait():
            for copy in page_writes():
                copy.wait()

        o_ref[0, 0] = (acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)
                       ).astype(o_ref.dtype)


def paged_attention(q, k, v, k_pages, v_pages, layer, block_tables,
                    lengths, *, window: int = 0, interpret: bool = False):
    """One-token paged attention for a batch of slots, writing the step's
    K/V into the pool first.

    q: (b, hq, d) — one query token per slot; k, v: (b, hkv, d) — the
    slot's new K/V, for position ``lengths - 1``; k_pages, v_pages:
    (n_layers, hkv, n_pages, d, block_tokens); layer: int32 scalar, the
    pool layer to read and write; block_tables: (b, nb) int32; lengths:
    (b,) int32 valid positions per slot, the new one included (0 =
    inactive row: no write, output zeros).  hq % hkv == 0 (GQA).
    Returns (out (b, hq, d) in q.dtype, k_pages, v_pages); the pools are
    updated in place (aliased), softmax/accumulation in fp32.
    """
    b, hq, d = q.shape
    _, hkv, _, _, block_tokens = k_pages.shape
    assert hq % hkv == 0
    g = hq // hkv
    nb = block_tables.shape[1]
    qg = q.reshape(b, hkv, g, d)
    # (b, hkv, d) -> (hkv, d, b), rows in the lanes: one block, fetched
    # once a call
    kn = k.astype(k_pages.dtype).transpose(1, 2, 0)
    vn = v.astype(v_pages.dtype).transpose(1, 2, 0)
    page_spec = pl.BlockSpec(
        (1, 1, 1, d, block_tokens),
        lambda ib, ih, ik, bt, ln, li: (li[0], ih, bt[ib, ik], 0, 0))
    row_spec = pl.BlockSpec((hkv, d, b),
                            lambda ib, ih, ik, bt, ln, li: (0, 0, 0))
    pool_spec = pl.BlockSpec(memory_space=pl.ANY)

    kernel = functools.partial(_kernel, scale=d ** -0.5,
                               block_tokens=block_tokens, window=window)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b, hkv, nb),
        in_specs=[
            pl.BlockSpec((1, 1, g, d),
                         lambda ib, ih, ik, bt, ln, li: (ib, ih, 0, 0)),
            row_spec, row_spec, page_spec, page_spec,
        ],
        out_specs=[
            pl.BlockSpec((1, 1, g, d),
                         lambda ib, ih, ik, bt, ln, li: (ib, ih, 0, 0)),
            pool_spec, pool_spec,
        ],
        scratch_shapes=[
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, d), jnp.float32),
            pltpu.VMEM((d, block_tokens), k_pages.dtype),
            pltpu.VMEM((d, block_tokens), v_pages.dtype),
            pltpu.SemaphoreType.DMA((2,)),
        ],
    )
    out, k_pages, v_pages = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((b, hkv, g, d), q.dtype),
                   jax.ShapeDtypeStruct(k_pages.shape, k_pages.dtype),
                   jax.ShapeDtypeStruct(v_pages.shape, v_pages.dtype)],
        # operands: 3 scalar-prefetch, q, k, v, k_pages (6), v_pages (7)
        input_output_aliases={6: 1, 7: 2},
        interpret=interpret,
    )(block_tables.astype(jnp.int32), lengths.astype(jnp.int32),
      jnp.asarray(layer, jnp.int32).reshape(1), qg, kn, vn, k_pages, v_pages)
    return out.reshape(b, hq, d), k_pages, v_pages
