"""Batched real-model executor: one jitted decode at fixed width over the
paged KV pool, zero recompilation across admission/detach.

Where :class:`repro.serve.jax_executor.JaxSlotExecutor` runs per-slot
batch-1 decode (N kernel launches per iteration, no MXU batching), this
executor owns block-table-backed KV storage shared with the engine's
:class:`repro.serve.kv_cache.PagedKVCache` allocator and decodes every
live slot in ONE jitted call:

  * **fixed batch width** — the decode function is jitted once at
    ``n_slots`` rows; a live request is a *row assignment*, admission
    pops a free row, detach pushes it back.  Inactive rows carry
    ``length == 0`` and an all-null block table, so they mask out inside
    the paged-attention kernel instead of changing any shape;
  * **block-table ABI** — the engine allocates/grows/frees block tables
    on ``self.kv``; before each decode the executor re-reads the live
    tables and sequence lengths into its fixed (W, nb_max) host arrays,
    so allocator state IS the kernel's gather map (one extra *null* page
    fills the tables of inactive rows, which read and write nothing);
  * **one stacked pool** — K and V each live in one
    ``(n_layers, hkv, n_pages, head_dim, block_tokens)`` array (see
    ``transformer.paged_kv_shape``); each layer's paged-attention call
    takes the whole pool and its layer index, and writes the step's K/V
    into it in place;
  * **prefill reuse** — prompts run through the same batch-1 jitted
    prefill as the per-slot executor (bitwise-identical first token),
    then the collected cache scatters into this request's pages.

Construct the engine with ``kv_cache=executor.kv`` — the allocator must
be shared or the gather map and the bookkeeping drift apart.

MoE configs decode with ``capacity_factor`` raised to ``num_experts``
(drop-free routing): at fixed width W a garbage inactive row must never
evict an active token from an expert buffer, and a capacity that admits
every assignment makes each row's expert output independent of its
batch neighbours — the token-identity-vs-per-slot property the tests
pin.

``encdec``/``vlm``/``hybrid``/``ssm`` families resist paging (encoder
context / recurrent state outside the block tables); ``make_executor``
falls back to the per-slot executor for them.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable, Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.paged_attention.paged_attention import page_span
from repro.models import model, transformer
from repro.serve.kv_cache import BLOCK_TOKENS, PagedKVCache
from repro.serve.spans import span


class JaxBatchedExecutor:
    """Fixed-width batched paged decode for the continuous engine."""

    def __init__(self, cfg, max_len: int, n_slots: int,
                 clock: Callable[[], float] = time.monotonic,
                 attn_impl: str = "auto", interpret: bool = False):
        if not model.supports_paged_decode(cfg, max_len):
            raise ValueError(
                f"family {cfg.family!r} (window={cfg.attention_window}) "
                f"does not support paged decode; use JaxSlotExecutor")
        self.cfg = cfg
        self.max_len = max_len
        self.n_slots = n_slots
        self.clock = clock
        self.block_tokens = BLOCK_TOKENS
        self.nb_max = -(-max_len // self.block_tokens)
        n_blocks = n_slots * self.nb_max
        # the allocator the engine must share (kv_cache=executor.kv)
        self.kv = PagedKVCache(n_blocks, self.block_tokens)
        self.null_page = n_blocks          # pool holds n_blocks + 1 pages
        shape = transformer.paged_kv_shape(cfg, n_blocks + 1,
                                           self.block_tokens)
        self._kp = jnp.zeros(shape, cfg.compute_dtype)
        self._vp = jnp.zeros(shape, cfg.compute_dtype)

        self.params = model.init_params(cfg, jax.random.key(0))
        # decode-time MoE capacity admits every assignment (see module doc)
        cfg_dec = cfg
        if cfg.num_experts > 0:
            cfg_dec = dataclasses.replace(
                cfg, capacity_factor=max(cfg.capacity_factor,
                                         float(cfg.num_experts)))
        # named, so the trace shows them as jit_prefill and
        # jit_scatter_prefill_pages
        def prefill(p, b):
            return transformer.prefill(p, b, cfg, max_len=max_len)

        def scatter_prefill_pages(c, kp, vp, pg, off):
            return transformer.scatter_prefill_pages(c, cfg, kp, vp, pg, off)

        self._prefill = jax.jit(prefill)
        self._scatter = jax.jit(scatter_prefill_pages, donate_argnums=(1, 2))

        step = model.paged_decode_fn(cfg_dec, attn_impl=attn_impl,
                                     interpret=interpret)

        def _step(p, tok, lens, kp, vp, bt):
            logits, kp, vp = step(p, tok, lens, kp, vp, bt)
            return jnp.argmax(logits, -1).astype(jnp.int32), kp, vp

        # the ONE decode compile: fixed (W,)/(W, nb_max) shapes forever
        self._decode = jax.jit(_step, donate_argnums=(3, 4))

        # host-side row state (fixed width W)
        self.rows: Dict[int, int] = {}              # rid -> row
        self._free_rows: List[int] = list(range(n_slots - 1, -1, -1))
        self._tok = np.zeros((n_slots,), np.int32)
        self._len = np.zeros((n_slots,), np.int32)
        self._tables = np.full((n_slots, self.nb_max), self.null_page,
                               np.int32)
        # running totals over decode calls: pages the paged kernel walks
        # (each live row's pages), and what a (rows, pages) grid steps
        self.pages_walked = 0
        self.pages_gridded = 0

    # ---- introspection ----------------------------------------------------
    def decode_compiles(self) -> int:
        """Compile count of the batched decode (the zero-recompile probe)."""
        return self._decode._cache_size()

    # ---- executor protocol ------------------------------------------------
    def _batch1(self, req):
        if req.prompt is None:
            raise ValueError(f"request {req.rid} carries no prompt tokens")
        return {"tokens": jnp.asarray(np.asarray(req.prompt)[None, :])}

    def prefill(self, reqs: Sequence) -> Tuple[List[int], float]:
        t0 = self.clock()
        with span("serve.prefill"):
            pend = []
            for r in reqs:
                with span("serve.prefill.request"):
                    row = self._free_rows.pop()
                    self.rows[r.rid] = row
                    logits, cache = self._prefill(self.params,
                                                  self._batch1(r))
                    tok = jnp.argmax(logits, -1).astype(jnp.int32)
                    table = self.kv.block_table(r.rid)  # engine allocated
                    s = int(np.asarray(r.prompt).shape[-1])
                    pos = np.arange(s)
                    page_ids = jnp.asarray(np.asarray(table, np.int32)
                                           [pos // self.block_tokens])
                    offs = jnp.asarray(
                        (pos % self.block_tokens).astype(np.int32))
                    self._kp, self._vp = self._scatter(
                        cache, self._kp, self._vp, page_ids, offs)
                    self._len[row] = s
                    pend.append((r, row, tok))
            with span("serve.prefill.sync"):
                if pend:
                    jax.block_until_ready([t for _, _, t in pend])
                cost = max(0.0, self.clock() - t0)
                toks = []
                for r, row, tok in pend:
                    t = int(tok[0])
                    self._tok[row] = t
                    toks.append(t)
        return toks, cost

    def decode(self, reqs: Sequence) -> Tuple[List[int], float]:
        t0 = self.clock()
        for r in reqs:
            self._len[self.rows[r.rid]] = self.kv.seq_len(r.rid)
        # per layer: the paged kernel walks each live row's pages, where a
        # (rows, pages) grid would step every page of every row
        first, stop = page_span(self._len, self.block_tokens,
                                self.cfg.attention_window or 0)
        walked = int(np.sum(stop - first))
        gridded = self.n_slots * self.nb_max
        self.pages_walked += walked
        self.pages_gridded += gridded
        with span("serve.decode", pages_walked=walked,
                  pages_gridded=gridded):
            with span("serve.decode.prepare"):
                # refresh the gather map from the allocator (the engine's
                # append_token may have claimed fresh blocks since last step)
                for r in reqs:
                    row = self.rows[r.rid]
                    table = self.kv.block_table(r.rid)
                    self._tables[row, :len(table)] = table
                args = (jnp.asarray(self._tok), jnp.asarray(self._len),
                        self._kp, self._vp, jnp.asarray(self._tables))
            with span("serve.decode.dispatch"):
                tok, self._kp, self._vp = self._decode(self.params, *args)
            with span("serve.decode.sync"):
                tok_np = np.asarray(jax.block_until_ready(tok))
                cost = max(0.0, self.clock() - t0)
                self._tok = tok_np.copy()
                return [int(tok_np[self.rows[r.rid]]) for r in reqs], cost

    def release(self, req) -> None:
        row = self.rows.pop(req.rid, None)
        if row is None:
            return
        self._free_rows.append(row)
        self._tok[row] = 0
        self._len[row] = 0
        self._tables[row, :] = self.null_page


def make_executor(cfg, max_len: int, n_slots: int,
                  clock: Callable[[], float] = time.monotonic,
                  attn_impl: str = "auto", interpret: bool = False):
    """Batched paged executor when the family supports it, else the
    per-slot fallback.  Returns (executor, kv_cache-or-None): pass the
    kv cache (the batched executor's own allocator) to the engine."""
    if model.supports_paged_decode(cfg, max_len):
        ex = JaxBatchedExecutor(cfg, max_len, n_slots, clock=clock,
                                attn_impl=attn_impl, interpret=interpret)
        return ex, ex.kv
    from repro.serve.jax_executor import JaxSlotExecutor

    return JaxSlotExecutor(cfg, max_len, clock=clock), None
