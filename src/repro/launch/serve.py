"""Serving launcher: continuous-batching inference engine with MPG + SLO
accounting.

    PYTHONPATH=src python -m repro.launch.serve --arch smollm-135m --smoke \
        --requests 16 --prompt-len 32 --max-new 16

Two engines share one accounting contract (``repro.core.ledger``):

  * ``--engine continuous`` (default): the production path —
    ``repro.serve.ContinuousServeEngine`` driving the real model through
    the batched paged-decode executor
    (``repro.serve.batched_executor``, one jitted decode over the
    allocator's block tables) or the per-slot fallback
    (``repro.serve.jax_executor``, ``--executor slot`` or families that
    resist paging), with per-iteration admission, immediate detach, a
    paged KV-cache allocator, and a latency SLO whose breaches book as
    scheduling-layer losses;
  * ``--engine static``: the legacy fixed-group batch loop (``Server``
    below), kept as the measured baseline the A/B benchmarks compare
    against.

Each batch slot is accounted like a chip: queue wait is QUEUED, prefill
is INIT, decode iterations a request actually uses are STEP (or
SLO_BREACH past its deadline), and batch bubbles are IDLE.  Serving's
fluctuating demand is why the paper's Fig. 15 shows lower serve RG than
training.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import time
from typing import Callable, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ARCH_IDS, get_config, get_smoke
from repro.core.goodput import Layer, Phase
from repro.core.ledger import GoodputLedger
from repro.models import model, transformer
from repro.runtime.compile_cache import enable_persistent_cache


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray
    max_new: int
    out_tokens: List[int] = dataclasses.field(default_factory=list)
    t_submit: float = 0.0
    t_first: float = 0.0
    t_done: float = 0.0

    @property
    def is_pad(self) -> bool:
        """Sentinel clones that fill a tail batch; excluded from metrics."""
        return self.rid < 0


def pad_group(group: List[Request], batch: int) -> List[Request]:
    """Pad a tail batch to full width with sentinel clones.

    The clones share prompts (the compiled program needs a full batch of
    real token ids) but carry ``rid=-1`` and their *own* ``out_tokens``
    lists, so ``run_batch`` neither appends generated tokens to a real
    request twice nor overwrites its ``t_first``/``t_done`` — the
    double-counted ``tokens_generated``/``throughput_tok_s`` bug.
    """
    if not group:
        # the modulo clone-source cycle below would divide by zero; an
        # all-pad batch also has no real prompts to clone from
        raise ValueError("cannot pad an empty request group")
    pads = [Request(rid=-1, prompt=group[i % len(group)].prompt,
                    max_new=group[i % len(group)].max_new)
            for i in range(batch - len(group))]
    return group + pads


class TickClock:
    """Deterministic stand-in for ``time.monotonic``: each call advances a
    fixed virtual dt.  Injecting one makes the serve emitter's interval
    stream reproducible (the determinism-audit fix for wall-clock reads),
    so serve-layer traces can be recorded and replayed like fleet ones."""

    def __init__(self, dt: float = 1.0, t0: float = 0.0):
        self.dt = dt
        self.t = t0

    def __call__(self) -> float:
        self.t += self.dt
        return self.t


class Server:
    """The static fixed-group batch loop (the measured A/B baseline).

    Clock discipline: ``run_batch`` reads ``self.clock`` exactly once per
    phase boundary (batch start, prefill end, decode end) so an injected
    ``TickClock`` advances identically on every same-seed run, and
    ``t_first``/``t_done`` land *inside* the emitted intervals — the
    serve-clock-skew fix.  Each slot's INIT/STEP/IDLE intervals exactly
    tile ``[t0, t2]`` (asserted per batch).
    """

    def __init__(self, cfg, batch: int, max_len: int,
                 ledger: Optional[GoodputLedger] = None,
                 clock: Callable[[], float] = time.monotonic):
        if batch <= 0:
            raise ValueError(f"batch must be positive, got {batch}")
        self.cfg = cfg
        self.batch = batch
        self.clock = clock
        self.ledger = ledger if ledger is not None else GoodputLedger()
        self.params = model.init_params(cfg, jax.random.key(0))
        self.prefill = jax.jit(
            lambda p, b: transformer.prefill(p, b, cfg, max_len=max_len)
            if cfg.family != "encdec" else model.prefill_fn(cfg)(p, b))
        self.decode = jax.jit(model.decode_fn(cfg))
        # ledger-time-base span of all emitted batches, for the capacity
        # denominator (SG): request wall-clock timestamps are the wrong
        # time base once a virtual clock is injected
        self._t_start: Optional[float] = None
        self._t_end: Optional[float] = None

    def capacity_chip_time(self) -> float:
        """Slot-chips x the ledger-time span this server was serving —
        the SG denominator, derived from the same clock the emitted
        intervals use (never from request timestamps)."""
        if self._t_start is None or self._t_end is None:
            return 0.0
        return self.batch * max(0.0, self._t_end - self._t_start)

    def span(self) -> float:
        if self._t_start is None or self._t_end is None:
            return 0.0
        return max(0.0, self._t_end - self._t_start)

    def _emit(self, rid: int, phase: Phase, t0: float, t1: float,
              layer: Layer, chips: int = 1):
        self.ledger.emit(job_id=f"req{rid}" if rid >= 0 else "pad",
                         phase=phase, t0=t0, t1=t1, chips=chips,
                         segment={"phase_kind": "serve",
                                  "arch": self.cfg.name,
                                  "emitter": "serve",
                                  "layer": layer.value})

    def run_batch(self, reqs: List[Request]) -> Tuple[float, float]:
        if len(reqs) != self.batch:
            raise ValueError(
                f"run_batch needs exactly batch={self.batch} slots, got "
                f"{len(reqs)} — pad tail groups with pad_group()")
        real = [r for r in reqs if not r.is_pad]
        n_pad = len(reqs) - len(real)
        if not real:
            raise ValueError("run_batch needs at least one real request")
        toks = np.stack([r.prompt for r in reqs])
        t0 = self.clock()                    # boundary 1: batch start
        for r in real:                       # queue wait: submit -> batch
            self._emit(r.rid, Phase.QUEUED, r.t_submit, t0,
                       layer=Layer.SCHEDULING)
        start_len = [len(r.out_tokens) for r in reqs]
        batch = {"tokens": jnp.asarray(toks)}
        if self.cfg.family == "vlm":
            batch["patches"] = jnp.zeros(
                (len(reqs), self.cfg.num_patches, self.cfg.d_model),
                self.cfg.compute_dtype)
        if self.cfg.family == "encdec":
            batch["frames"] = jnp.zeros(
                (len(reqs), self.cfg.encoder_positions, self.cfg.d_model),
                self.cfg.compute_dtype)
        logits, cache = self.prefill(self.params, batch)
        tok = jnp.argmax(logits, -1).astype(jnp.int32)
        jax.block_until_ready(tok)
        t1 = self.clock()                    # boundary 2: prefill end
        for r, t in zip(reqs, np.asarray(tok)):
            r.out_tokens.append(int(t))
            if not r.is_pad:
                r.t_first = t1               # first token lands here
        max_new = max(r.max_new for r in reqs)
        for _ in range(max_new - 1):
            logits, cache = self.decode(self.params, tok, cache)
            tok = jnp.argmax(logits, -1).astype(jnp.int32)
            for r, t in zip(reqs, np.asarray(tok)):
                if len(r.out_tokens) < r.max_new:
                    r.out_tokens.append(int(t))
        jax.block_until_ready(tok)
        t2 = self.clock()                    # boundary 3: decode end
        t_prefill = t1 - t0
        t_decode = t2 - t1
        iters = max(max_new - 1, 1)
        gen = {id(r): len(r.out_tokens) - s for r, s in zip(reqs, start_len)}
        for r in real:
            r.t_done = t2
            # prefill is program setup for the batch: INIT for live slots
            # (model-layer warmup — real forward compute, not a compile);
            # STEP for the decode iterations this request consumed, IDLE
            # for the bubble riding out the batch's longest request
            frac = min(1.0, max(0, gen[id(r)] - 1) / iters)
            split = t1 + frac * t_decode
            self._assert_tiles(t0, (t0, t1, split, t2), t2)
            self._emit(r.rid, Phase.INIT, t0, t1, layer=Layer.MODEL)
            self._emit(r.rid, Phase.STEP, t1, split, layer=Layer.MODEL)
            self._emit(r.rid, Phase.IDLE, split, t2,
                       layer=Layer.SCHEDULING)
        if n_pad:
            # padded slots: a batch-shape bubble the batching policy —
            # the scheduling layer — is responsible for
            self._emit(-1, Phase.IDLE, t0, t2, layer=Layer.SCHEDULING,
                       chips=n_pad)
        if self._t_start is None:
            self._t_start = t0
        self._t_end = t2
        return t_prefill, t_decode

    @staticmethod
    def _assert_tiles(t0: float, bounds: Tuple[float, ...], t2: float):
        """Each slot's interval boundaries must tile [t0, t2]: start at
        t0, end at t2, monotone non-decreasing — no gap, no overlap
        (zero-width segments are legal boundaries, not gaps)."""
        assert bounds[0] == t0 and bounds[-1] == t2, \
            f"slot intervals do not span [{t0}, {t2}]: {bounds}"
        for a, b in zip(bounds, bounds[1:]):
            assert a <= b, f"slot interval boundaries regress: {bounds}"


def run_static_server(cfg, reqs: List[Request], batch: int, max_new: int,
                      prompt_len: int,
                      ledger: Optional[GoodputLedger] = None,
                      clock: Callable[[], float] = time.monotonic
                      ) -> Tuple["Server", dict]:
    """Drive the legacy fixed-group loop and summarize it (CLI + tests)."""
    ledger = ledger if ledger is not None else GoodputLedger(window=60.0)
    server = Server(cfg, batch, max_len=prompt_len + max_new,
                    ledger=ledger, clock=clock)
    t_pre = t_dec = 0.0
    for i in range(0, len(reqs), batch):
        group = pad_group(reqs[i:i + batch], batch)
        p, d = server.run_batch(group)
        t_pre += p
        t_dec += d
    done = [r for r in reqs if r.out_tokens]
    toks = sum(len(r.out_tokens) for r in done)
    wall = server.span()
    ttft = (float(np.mean([r.t_first - r.t_submit for r in done]))
            if done else 0.0)
    rep = ledger.report(capacity_chip_time=server.capacity_chip_time())
    return server, {
        "engine": "static",
        "arch": cfg.name,
        "requests": len(done),
        "tokens_generated": toks,
        "throughput_tok_s": round(toks / wall, 2) if wall > 0 else 0.0,
        "mean_ttft_s": round(ttft, 4),
        "prefill_s": round(t_pre, 3),
        "decode_s": round(t_dec, 3),
        "capacity_chip_time": server.capacity_chip_time(),
        "serve_sg": round(rep.sg, 4),
        "serve_rg": round(rep.rg, 4),
        "rg_breakdown": {k: round(v, 4)
                         for k, v in ledger.rg_breakdown().items()},
    }


def run_continuous_server(cfg, reqs: List[Request], batch: int,
                          max_new: int, prompt_len: int,
                          slo_ttft: float, slo_tpot: float,
                          kv_block_tokens: int = 0,
                          clock: Callable[[], float] = time.monotonic,
                          executor_kind: str = "auto") -> Tuple[object, dict]:
    """Drive the continuous engine over the real model and return
    ``(executor, ServeReport dict)``.  ``executor_kind``: "batched"
    decodes every live slot in one jitted call over the paged KV pool,
    "slot" runs the per-slot batch-1 fallback, "auto" picks batched when
    the family supports paged decode."""
    from repro.models import model as _model
    from repro.serve import (ContinuousServeEngine, PagedKVCache,
                             ServeRequest, ServeSLO)

    slo = ServeSLO(ttft=slo_ttft if slo_ttft > 0 else float("inf"),
                   tpot=slo_tpot if slo_tpot > 0 else float("inf"))
    max_len = prompt_len + max_new
    use_batched = executor_kind == "batched" or (
        executor_kind == "auto"
        and _model.supports_paged_decode(cfg, max_len))
    if use_batched:
        from repro.serve.batched_executor import JaxBatchedExecutor

        # the batched executor's allocator IS the engine's kv cache
        # (block_tokens pinned to the kernel kv tile, so kv_block_tokens
        # is ignored on this path)
        executor = JaxBatchedExecutor(cfg, max_len, batch, clock=clock)
        kv = executor.kv
    else:
        from repro.serve.jax_executor import JaxSlotExecutor

        block_tokens = kv_block_tokens or min(128, prompt_len + max_new)
        need_blocks = -(-(prompt_len + max_new) // block_tokens)
        kv = PagedKVCache(n_blocks=batch * need_blocks,
                          block_tokens=block_tokens)
        executor = JaxSlotExecutor(cfg, max_len=max_len, clock=clock)
    engine = ContinuousServeEngine(batch, executor, slo=slo, kv_cache=kv,
                                   ledger=GoodputLedger(window=60.0),
                                   arch=cfg.name)
    serve_reqs = [ServeRequest(rid=r.rid, prompt_len=len(r.prompt),
                               max_new=r.max_new, t_submit=r.t_submit,
                               prompt=r.prompt)
                  for r in reqs]
    report = engine.run(serve_reqs)
    # reflect results back onto the caller's Request objects
    by_rid = {r.rid: r for r in serve_reqs}
    for r in reqs:
        sr = by_rid[r.rid]
        r.out_tokens = sr.out_tokens
        r.t_first, r.t_done = sr.t_first, sr.t_done
    out = report.as_dict()
    out["arch"] = cfg.name
    return executor, out


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-135m", choices=list(ARCH_IDS))
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--engine", default="continuous",
                    choices=("continuous", "static"))
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--span", type=float, default=0.0,
                    help="spread request arrivals over this many seconds "
                         "of the serve timeline (0 = all at t=0)")
    ap.add_argument("--arrival", default="uniform",
                    choices=("uniform", "diurnal", "bursty"),
                    help="arrival modulation over --span (the fleet "
                         "scenario processes, repro.fleet.scenarios)")
    ap.add_argument("--executor", default="auto",
                    choices=("auto", "batched", "slot"),
                    help="continuous-engine executor: one jitted batched "
                         "paged decode vs per-slot batch-1 (auto picks "
                         "batched when the family supports paged decode)")
    ap.add_argument("--slo-ttft", type=float, default=0.0,
                    help="time-to-first-token SLO in seconds (0 = none)")
    ap.add_argument("--slo-tpot", type=float, default=0.0,
                    help="per-output-token SLO in seconds (0 = none)")
    ap.add_argument("--tick-dt", type=float, default=0.0,
                    help="inject a TickClock with this dt (deterministic "
                         "virtual time; 0 = wall clock)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    enable_persistent_cache()
    cfg = get_smoke(args.arch) if args.smoke else get_config(args.arch)
    clock = TickClock(dt=args.tick_dt) if args.tick_dt > 0 \
        else time.monotonic
    rng = np.random.default_rng(args.seed)
    if args.span > 0:
        from repro.fleet.scenarios import SCENARIOS, request_arrivals
        mod = {"uniform": SCENARIOS["steady"],
               "diurnal": SCENARIOS["diurnal"],
               "bursty": SCENARIOS["bursty"]}[args.arrival].arrival
        arrivals = request_arrivals(args.requests, args.span,
                                    seed=args.seed, arrival=mod)
    else:
        arrivals = [0.0] * args.requests
    # Arrivals are offsets from the start of the serve timeline; anchor
    # them to the clock actually driving the server so t_submit shares a
    # time base with the emitted intervals (wall clock reads machine
    # uptime, not zero).
    t_base = clock()
    reqs = [Request(i, rng.integers(0, cfg.vocab_size,
                                    args.prompt_len).astype(np.int32),
                    args.max_new, t_submit=t_base + arrivals[i])
            for i in range(args.requests)]

    if args.engine == "continuous":
        _, out = run_continuous_server(
            cfg, reqs, args.batch, args.max_new, args.prompt_len,
            slo_ttft=args.slo_ttft, slo_tpot=args.slo_tpot, clock=clock,
            executor_kind=args.executor)
    else:
        _, out = run_static_server(cfg, reqs, args.batch, args.max_new,
                                   args.prompt_len, clock=clock)
    print(json.dumps(out, indent=1))


if __name__ == "__main__":
    main()
