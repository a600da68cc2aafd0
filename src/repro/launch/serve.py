"""Serving launcher: the continuous-batching engine over the real model,
with MPG + SLO accounting.

    PYTHONPATH=src python -m repro.launch.serve --arch smollm-135m --smoke \
        --requests 16 --prompt-len 32 --max-new 16

``repro.serve.ContinuousServeEngine`` admits requests every iteration,
detaches finished ones at once, and gates admission on a paged KV-cache
allocator.  ``repro.serve.batched_executor.make_executor`` picks its
executor by family: one jitted batched decode over the allocator's block
tables where the family supports paged decode
(``repro.models.model.supports_paged_decode``), whose allocator the
engine shares; else per-slot batch-1 decode, for which the engine sizes
its own cache.

Each batch slot is accounted like a chip: queue wait is QUEUED, prefill
is INIT, on-time decode is STEP (SLO_BREACH past its deadline), and
slot-time no op covers is IDLE.  Serving's fluctuating demand is why the
paper's Fig. 15 shows lower serve RG than training.
"""
from __future__ import annotations

import argparse
import json
import time
from typing import Callable, List, Tuple

import numpy as np

from repro.configs import ARCH_IDS, get_config, get_smoke
from repro.core.ledger import GoodputLedger
from repro.runtime.compile_cache import enable_persistent_cache
from repro.serve import ContinuousServeEngine, ServeRequest, ServeSLO
from repro.serve.batched_executor import make_executor


class TickClock:
    """Deterministic stand-in for ``time.monotonic``: each call advances a
    fixed virtual dt.  Injecting one makes the executor's op costs, and
    so the serve emitter's interval stream, reproducible, so serve-layer
    traces can be recorded and replayed like fleet ones."""

    def __init__(self, dt: float = 1.0, t0: float = 0.0):
        self.dt = dt
        self.t = t0

    def __call__(self) -> float:
        self.t += self.dt
        return self.t


def run_continuous_server(cfg, reqs: List[ServeRequest], batch: int,
                          slo_ttft: float, slo_tpot: float,
                          clock: Callable[[], float] = time.monotonic
                          ) -> Tuple[ContinuousServeEngine, dict]:
    """Serve ``reqs`` on the continuous engine over the real model and
    return ``(engine, ServeReport dict)``; the engine holds the executor
    ``make_executor`` picked, its KV cache and its ledger."""
    if batch <= 0:
        raise ValueError(f"batch must be positive, got {batch}")
    slo = ServeSLO(ttft=slo_ttft if slo_ttft > 0 else float("inf"),
                   tpot=slo_tpot if slo_tpot > 0 else float("inf"))
    max_len = max((r.prompt_len + r.max_new for r in reqs), default=1)
    executor, kv = make_executor(cfg, max_len, batch, clock=clock)
    engine = ContinuousServeEngine(batch, executor, slo=slo, kv_cache=kv,
                                   ledger=GoodputLedger(window=60.0),
                                   arch=cfg.name)
    out = engine.run(reqs).as_dict()
    out["arch"] = cfg.name
    return engine, out


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-135m", choices=list(ARCH_IDS))
    ap.add_argument("--smoke", action="store_true")
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
    reqs = [ServeRequest(rid=i, prompt_len=args.prompt_len,
                         max_new=args.max_new, t_submit=t_base + arrivals[i],
                         prompt=rng.integers(0, cfg.vocab_size,
                                             args.prompt_len)
                         .astype(np.int32))
            for i in range(args.requests)]
    _, out = run_continuous_server(cfg, reqs, args.batch,
                                   slo_ttft=args.slo_ttft,
                                   slo_tpot=args.slo_tpot, clock=clock)
    print(json.dumps(out, indent=1))


if __name__ == "__main__":
    main()
