#!/usr/bin/env python3
"""One-process sweeps that fix a serving cell's numbers on the chip; the
benchmark's own runs never call this.

    python3 benchmarks/chip/calibrate.py knee --workload <cell> \\
        --seed <n> --seconds <s> --rates 10,14,20
    python3 benchmarks/chip/calibrate.py limits --workload <cell> \\
        --seconds <s> --seeds 1,2,3
    python3 benchmarks/chip/calibrate.py control --workload <cell> \\
        --seconds <s> --seeds 1,2,3

``knee`` serves the cell's mix at each rate for one window cut at its end
and prints the offered and delivered output tokens/s (delivered also
over the window after its first ``--ramp`` seconds, when the system has
filled) and the admission queue at the end of the ramp and of the
window.  The knee is the highest rate at which delivery keeps up and the
queue does not grow.

``limits`` serves one window of the cell's own mix for each seed, with
that seed's weights, and prints the widest reference-logit gap of the
served tokens beside that of the fp8 control's first choices on the same
prompts and tokens: the two readings a cell's limit is set between.

``control`` makes whole runs of the cell through ``run.run_cell`` with
the fp8 control in the program's place in the comparison, one for each
seed, and prints whether each came out correct: it must not.
"""
from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

import spec  # noqa: E402

def queue_at(reqs, t):
    return sum(1 for r in reqs
               if r.t_submit <= t and (r.t_first == 0 or r.t_admit > t))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("mode", choices=("knee", "limits", "control"))
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--rates", default="")
    ap.add_argument("--control", type=int, default=1)
    ap.add_argument("--ramp", type=float, default=5.0)
    args = ap.parse_args(argv)

    bench = spec.load_benchmark()
    wl = spec.workload(bench, args.workload)
    sys.path.insert(0, str(spec.ROOT / "src"))
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(spec.ROOT / ".jax_cache")
    import jax
    from repro.runtime.compile_cache import enable_persistent_cache

    if jax.devices()[0].platform != "tpu":
        print("calibrate.py: needs a TPU", file=sys.stderr)
        return 2
    enable_persistent_cache()
    import numpy as np
    import peaks
    import run
    import serve_cell
    import traffic

    dev = jax.devices()[0]
    ctx = run.Ctx(bench=bench, workload=wl,
                  cfile=spec.config_file(bench, wl["config"]),
                  traffic=traffic.load(wl["traffic"]), seed=args.seed,
                  seconds=args.seconds, trace=False, t_start=T_START,
                  peak=peaks.peak(dev.device_kind))
    mix = ctx.traffic
    if args.mode == "control":
        for seed in [int(x) for x in args.seeds.split(",")]:
            res = run.run_cell(dataclasses.replace(
                ctx, seed=seed, control=True, t_start=time.monotonic()))
            print("control " + json.dumps({
                "seed": seed, "correct": res["correct"],
                "checks": res["checks"]}), flush=True)
            gc.collect()
        return 0
    srv = serve_cell.Server(ctx)
    if args.mode == "knee":
        t0 = time.monotonic()
        srv.load(args.seed)
        t1 = time.monotonic()
        srv.warm(mix, args.seed)
        print("setup " + json.dumps({
            "to_executor": t0 - T_START, "weights": t1 - t0,
            "warm": time.monotonic() - t1}), flush=True)
        mean_out = float(np.mean(traffic.output_lengths(mix, 1000)))
        for rate in [float(x) for x in args.rates.split(",")]:
            m = dict(mix, rate_per_s=rate, follow="window")
            res, facts, sreqs = srv.window(m, args.seed, args.seconds, False,
                                           time.monotonic(), ctx.peak)
            srv.reset(sreqs)
            late = sum(1 for r in res.requests for t in r.token_times
                       if args.ramp <= t <= args.seconds)
            out = {"rate": rate, "offered_tokens_per_s": rate * mean_out,
                   "delivered_tokens_per_s":
                       spec.reader("output_tokens_per_s")(res),
                   "delivered_after_ramp":
                       late / (args.seconds - args.ramp),
                   "queue_ramp": queue_at(res.requests, args.ramp),
                   "queue_end": queue_at(res.requests, args.seconds),
                   "decode_call_ms": spec.reader("decode_call_ms.serve")(res),
                   "prefill_call_ms":
                       spec.reader("prefill_call_ms.serve")(res),
                   "ttft_p95_s_cut": spec.reader("ttft_p95_s")(res),
                   **facts}
            print("knee " + json.dumps(out), flush=True)
        return 0
    for seed in [int(x) for x in args.seeds.split(",")]:
        t0 = time.monotonic()
        srv.load(seed)
        srv.warm(mix, seed)
        res, facts, sreqs = srv.window(mix, seed, args.seconds, False,
                                       time.monotonic(), ctx.peak)
        picked = serve_cell.sample([r for r in sreqs if r.t_done > 0], seed)
        srv.reset(sreqs)
        srv.ex.params = None
        gc.collect()
        t1 = time.monotonic()
        gap, ctl = serve_cell.check(srv.fields, seed, srv.pdt, picked,
                                    srv.pad_to, control=bool(args.control))
        print("limits " + json.dumps({
            "seed": seed, "served_logit_gap": gap, "control_gap": ctl,
            "sampled_requests": len(picked),
            "sampled_tokens": sum(len(r.out_tokens) for r in picked),
            "window_s": t1 - t0, "reference_s": time.monotonic() - t1,
            "window_compiles": facts["window_jit_compiles"],
            "unfinished": facts["unfinished_in_window"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
