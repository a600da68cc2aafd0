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
page) — they are never read.  ``lengths[b] == 0`` marks an *inactive*
batch row: the kernel reads no page, writes nothing into the pool and
outputs zeros, which is what lets a fixed-width batched executor mask
empty rows instead of recompiling at a new width.

``block_tokens`` is read off the page pool's shape and **is** the
kernel's kv tile: each loop iteration attends over exactly one page, so
allocator blocks map 1:1 onto kernel iterations with no partial-tile
waste, and the tile is lane-dense at every head_dim.  The allocator's
default, ``repro.serve.kv_cache.BLOCK_TOKENS`` (128), is one full lane
row; the batched executor builds its pool at that size.

TPU adaptation notes: the grid is one step per batch row, ``(b,)``; the
q and output blocks hold all of the row's heads, ``(1, hkv, g, d)``.
``pltpu.PrefetchScalarGridSpec`` prefetches the block table, the length
vector and the layer index into SMEM.  The pools stay in HBM
(``pl.ANY``) and each row walks only the pages it can attend to,
``first .. last`` (:func:`page_span`), in a ``fori_loop`` whose trip
count is read from the row's length: an inactive row runs no iteration
and a page past the row's last one is never read.  Each iteration DMAs
one page of every kv head, ``pool[layer, :, block_tables[b, p]]``, into
one of two VMEM slots while the page before it is attended over (double
buffering), and runs, per head, the fp32 online softmax over a
``(g, d) x (d, block_tokens)`` tile.  At page ``last``, which holds
position ``lengths[b] - 1``, the kernel patches the new column into the
VMEM copy of every head, attends over the patched tile, and DMAs it
back over the pool's page; the pools are outputs aliased to their
inputs, and the write is waited for before the row's step ends.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

NEG_INF = -1e30


def page_span(length, block_tokens: int, window: int = 0):
    """``(first, stop)``: the block-table pages ``first .. stop - 1``
    that a row's single query, at position ``length - 1``, can attend to;
    none (``first == stop == 0``) for an inactive row, ``length == 0``.
    Plain arithmetic, so it takes ints, numpy arrays and traced scalars.
    """
    first = 0
    if window > 0:
        start = length - window          # the window's first position
        first = start * (start > 0) // block_tokens
    return first, (length + block_tokens - 1) // block_tokens


def _kernel(bt_ref, len_ref, li_ref, q_ref, kn_ref, vn_ref, k_ref, v_ref,
            o_ref, ko_ref, vo_ref, m_scr, l_scr, acc_scr, kbuf, vbuf, sems,
            *, scale: float, block_tokens: int, window: int):
    ib = pl.program_id(0)
    hkv = kbuf.shape[1]
    length = len_ref[ib]
    first, stop = page_span(length, block_tokens, window)
    # the page that takes this step's new token (position length - 1)
    last = stop - 1
    li = li_ref[0]

    def page_reads(p, slot):
        src = (li, slice(None), bt_ref[ib, p])
        return (pltpu.make_async_copy(k_ref.at[src], kbuf.at[slot],
                                      sems.at[0, slot]),
                pltpu.make_async_copy(v_ref.at[src], vbuf.at[slot],
                                      sems.at[1, slot]))

    def page_writes(slot):
        dst = (li, slice(None), bt_ref[ib, last])
        return (pltpu.make_async_copy(kbuf.at[slot], ko_ref.at[dst],
                                      sems.at[2, 0]),
                pltpu.make_async_copy(vbuf.at[slot], vo_ref.at[dst],
                                      sems.at[2, 1]))

    def patch(slot):
        """Write row ib's new K/V into column (length - 1) % bt of the
        slot's page, every head: (d, b) @ (b, bt) one-hot at (ib, off)
        (exact: each output is one product with 1.0)."""
        off = (length - 1) % block_tokens
        shape = (kn_ref.shape[2], block_tokens)
        put = ((jax.lax.broadcasted_iota(jnp.int32, shape, 0) == ib)
               & (jax.lax.broadcasted_iota(jnp.int32, shape, 1) == off)
               ).astype(jnp.float32)
        new = jax.lax.broadcasted_iota(jnp.int32, kbuf.shape[2:], 1) == off
        for buf, rows in ((kbuf, kn_ref), (vbuf, vn_ref)):
            for ih in range(hkv):
                col = jax.lax.dot_general(
                    rows[ih].astype(jnp.float32), put,
                    (((1,), (0,)), ((), ())),
                    precision=jax.lax.Precision.HIGHEST,
                    preferred_element_type=jnp.float32)
                buf[slot, ih] = jnp.where(new, col.astype(buf.dtype),
                                          buf[slot, ih])

    def attend(p, slot):
        """Online-softmax update of every head over page p's tile."""
        g = q_ref.shape[2]
        kv_pos = p * block_tokens + jax.lax.broadcasted_iota(
            jnp.int32, (g, block_tokens), 1)
        mask = kv_pos < length
        if window > 0:
            # the (single) query sits at absolute position length - 1
            mask &= kv_pos > (length - 1 - window)
        for ih in range(hkv):
            q = q_ref[0, ih].astype(jnp.float32) * scale          # (g, d)
            s = jax.lax.dot_general(
                q, kbuf[slot, ih].astype(jnp.float32),
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)               # (g, bt)
            s = jnp.where(mask, s, NEG_INF)
            m_prev = m_scr[ih]                                    # (g, 1)
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            e = jnp.exp(s - m_new)                                # (g, bt)
            alpha = jnp.exp(m_prev - m_new)                       # (g, 1)
            l_scr[ih] = alpha * l_scr[ih] + jnp.sum(e, axis=1,
                                                    keepdims=True)
            acc_scr[ih] = acc_scr[ih] * alpha + jax.lax.dot_general(
                e, vbuf[slot, ih].astype(jnp.float32),
                (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)               # (g, d)
            m_scr[ih] = m_new

    m_scr[...] = jnp.full_like(m_scr, NEG_INF)
    l_scr[...] = jnp.zeros_like(l_scr)
    acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(stop > first)
    def _prime():
        for copy in page_reads(first, 0):
            copy.start()

    def step(p, carry):
        slot = (p - first) % 2
        for copy in page_reads(p, slot):
            copy.wait()

        @pl.when(p + 1 < stop)
        def _prefetch():
            for copy in page_reads(p + 1, 1 - slot):
                copy.start()

        @pl.when(p == last)
        def _write():
            patch(slot)
            for copy in page_writes(slot):
                copy.start()

        attend(p, slot)
        return carry

    jax.lax.fori_loop(first, stop, step, 0)

    @pl.when(stop > first)
    def _wait_writes():
        for copy in page_writes((last - first) % 2):
            copy.wait()

    # an inactive row never touched the scratch: l == 0 and the output
    # is exact zeros, not a softmax over masked junk
    for ih in range(hkv):
        o_ref[0, ih] = (acc_scr[ih] / jnp.maximum(l_scr[ih], 1e-30)
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
    inactive row: no read, no write, output zeros).  hq % hkv == 0 (GQA).
    Returns (out (b, hq, d) in q.dtype, k_pages, v_pages); the pools are
    updated in place (aliased), softmax/accumulation in fp32.
    """
    b, hq, d = q.shape
    _, hkv, _, _, block_tokens = k_pages.shape
    assert hq % hkv == 0
    g = hq // hkv
    qg = q.reshape(b, hkv, g, d)
    # (b, hkv, d) -> (hkv, d, b), rows in the lanes: one block, fetched
    # once a call
    kn = k.astype(k_pages.dtype).transpose(1, 2, 0)
    vn = v.astype(v_pages.dtype).transpose(1, 2, 0)
    row_spec = pl.BlockSpec((hkv, d, b), lambda ib, bt, ln, li: (0, 0, 0))
    head_spec = pl.BlockSpec((1, hkv, g, d),
                             lambda ib, bt, ln, li: (ib, 0, 0, 0))
    pool_spec = pl.BlockSpec(memory_space=pl.ANY)

    kernel = functools.partial(_kernel, scale=d ** -0.5,
                               block_tokens=block_tokens, window=window)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b,),
        in_specs=[head_spec, row_spec, row_spec, pool_spec, pool_spec],
        out_specs=[head_spec, pool_spec, pool_spec],
        scratch_shapes=[
            pltpu.VMEM((hkv, g, 1), jnp.float32),
            pltpu.VMEM((hkv, g, 1), jnp.float32),
            pltpu.VMEM((hkv, g, d), jnp.float32),
            # two slots of one page of every kv head
            pltpu.VMEM((2, hkv, d, block_tokens), k_pages.dtype),
            pltpu.VMEM((2, hkv, d, block_tokens), v_pages.dtype),
            # K reads, V reads (a slot each), then the K and V writes
            pltpu.SemaphoreType.DMA((3, 2)),
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
