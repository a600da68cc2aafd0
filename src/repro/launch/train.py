"""Training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch smollm-135m --smoke \
        --steps 200 --batch 8 --seq 128

Runs the MPG-instrumented orchestrator (checkpoint/restart, async ckpt,
AOT cache) on the default backend: one device, no mesh.  The sharded step
on a device mesh is built by ``repro.launch.strategy.jit_train_step``.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import tempfile

from repro.configs import ARCH_IDS, get_config, get_smoke
from repro.core.goodput import compute_goodput, rg_breakdown
from repro.runtime.compile_cache import enable_persistent_cache
from repro.runtime.orchestrator import Orchestrator, RunConfig


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-135m", choices=list(ARCH_IDS))
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config (CPU-runnable)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--checkpoint-every", type=int, default=20)
    ap.add_argument("--async-checkpoint", action="store_true")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--preempt-at", type=int, default=None)
    args = ap.parse_args(argv)

    enable_persistent_cache()
    cfg = get_smoke(args.arch) if args.smoke else get_config(args.arch)
    ckpt_dir = args.ckpt_dir or tempfile.mkdtemp(prefix="repro_train_")
    run = RunConfig(steps=args.steps, batch=args.batch, seq=args.seq,
                    checkpoint_every=args.checkpoint_every,
                    async_checkpoint=args.async_checkpoint,
                    ckpt_dir=ckpt_dir, preempt_at_step=args.preempt_at,
                    job_id=f"train-{args.arch}")
    orc = Orchestrator(cfg, run)
    out = orc.run()

    total = sum(i.chip_time for i in orc.intervals)
    rep = compute_goodput(orc.intervals, total)
    print(json.dumps({
        "arch": args.arch,
        "steps": [out["start_step"], out["end_step"]],
        "final_loss": out["losses"][-1] if out["losses"] else None,
        "runtime_goodput": round(rep.rg, 4),
        "rg_breakdown": {k: round(v, 4)
                         for k, v in rg_breakdown(orc.intervals).items()},
        "ckpt": out["ckpt_metrics"],
        "compile_s": round(out["compile_s"], 2),
        "ckpt_dir": ckpt_dir,
    }, indent=1))


if __name__ == "__main__":
    main()
