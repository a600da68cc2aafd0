"""Real-model executor for the continuous-batching engine.

Per-slot batch-1 execution: each live request owns its own batch-1
decode cache, so admission and detach are cache-dict inserts/removes —
no recompilation, no cross-slot position coupling (the dense ring cache
shares one scalar ``pos`` across a batch, which is exactly what forbids
mid-flight admission into a *batched* cache).  ``prefill`` and
``decode`` are jitted once at batch width 1 and reused for every slot.

This trades MXU batching efficiency for exact continuous-batching
semantics with the real program.  It serves the families whose state
block tables do not address — recurrent state (``ssm``/``hybrid``),
encoder context (``encdec``/``vlm``) and windows narrower than the
sequence; ``repro.serve.batched_executor.make_executor`` sends every
other family to the batched paged-decode executor.

Costs are measured off an injectable clock (``TickClock`` for
deterministic tests, ``time.monotonic`` for real runs), read once at
each op's start and once after its single host sync.
"""
from __future__ import annotations

import time
from typing import Callable, Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import model, transformer


class JaxSlotExecutor:
    def __init__(self, cfg, max_len: int,
                 clock: Callable[[], float] = time.monotonic):
        self.cfg = cfg
        self.max_len = max_len
        self.clock = clock
        self.params = model.init_params(cfg, jax.random.key(0))
        self._prefill = jax.jit(
            lambda p, b: transformer.prefill(p, b, cfg, max_len=max_len)
            if cfg.family != "encdec" else model.prefill_fn(cfg)(p, b))
        self._decode = jax.jit(model.decode_fn(cfg))
        self._caches: Dict[int, object] = {}
        self._tok: Dict[int, object] = {}

    def _batch1(self, req) -> Dict[str, object]:
        if req.prompt is None:
            raise ValueError(f"request {req.rid} carries no prompt tokens")
        batch = {"tokens": jnp.asarray(np.asarray(req.prompt)[None, :])}
        cfg = self.cfg
        if cfg.family == "vlm":
            batch["patches"] = jnp.zeros(
                (1, cfg.num_patches, cfg.d_model), cfg.compute_dtype)
        if cfg.family == "encdec":
            batch["frames"] = jnp.zeros(
                (1, cfg.encoder_positions, cfg.d_model), cfg.compute_dtype)
        return batch

    def prefill(self, reqs: Sequence) -> Tuple[List[int], float]:
        t0 = self.clock()
        pend = []
        for r in reqs:
            logits, cache = self._prefill(self.params, self._batch1(r))
            tok = jnp.argmax(logits, -1).astype(jnp.int32)
            self._caches[r.rid] = cache
            self._tok[r.rid] = tok
            pend.append(tok)
        # issue every slot's computation first, then ONE host sync before
        # reading the clock — a per-slot int() would serialize N device
        # round-trips into the measured cost
        if pend:
            jax.block_until_ready(pend)
        cost = max(0.0, self.clock() - t0)
        return [int(t[0]) for t in pend], cost

    def decode(self, reqs: Sequence) -> Tuple[List[int], float]:
        t0 = self.clock()
        pend = []
        for r in reqs:
            logits, cache = self._decode(self.params, self._tok[r.rid],
                                         self._caches[r.rid])
            tok = jnp.argmax(logits, -1).astype(jnp.int32)
            self._caches[r.rid] = cache
            self._tok[r.rid] = tok
            pend.append(tok)
        if pend:
            jax.block_until_ready(pend)
        cost = max(0.0, self.clock() - t0)
        return [int(t[0]) for t in pend], cost

    def release(self, req) -> None:
        self._caches.pop(req.rid, None)
        self._tok.pop(req.rid, None)
