#!/usr/bin/env python3
"""Run one cell of the chip benchmark once.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \\
        --seconds <s> --trace <0|1>

The cell, its configuration and its traffic mix are found by name through
``BENCHMARK.json``; each metric is computed by its reader in
``metrics/<name>.py``.  With ``--trace 0`` the cell's end-to-end metrics
are reported, with ``--trace 1`` its per-layer metrics, read partly from a
profiler trace of part of the window.

It runs on the machine it is started on and refuses any but a TPU with as
many chips as the cell asks for.  The last line of standard output is one
JSON object: ``correct``, ``attempted``, ``failed``, ``metrics``,
``device`` (and ``breakdown`` when traced), then ``checks``, each number
compared beside its limit; the same comparisons end standard error.
"""
from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from typing import Callable, Dict, Optional  # noqa: E402

import spec  # noqa: E402

CACHE_DIR = spec.ROOT / ".jax_cache"


@dataclasses.dataclass
class Ctx:
    bench: Dict
    workload: Dict
    cfile: Dict
    traffic: Dict
    seed: int
    seconds: float
    trace: bool
    t_start: float
    smoke: bool = False
    # tests build a broken executor here; None builds the program's own
    make_executor: Optional[Callable] = None
    # the control: the fp8 reference's choices stand in for the program's
    # served tokens in the comparison (``calibrate.py control``, tests)
    control: bool = False
    memory_peak: Callable[[], int] = lambda: 0
    peak: object = None          # peaks.Peak of the chip; None off the chip


def memory_peak(devices) -> Callable[[], int]:
    def read():
        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                 for d in devices]
        return int(max(peaks))
    return read


def cell_module(kind: str):
    if kind == "serve":
        import serve_cell
        return serve_cell
    raise ValueError(f"unknown traffic kind {kind!r}")


def run_cell(ctx: Ctx) -> Dict:
    """Run the cell once and assemble its result line (no device check:
    ``main`` makes that before it calls here)."""
    out = cell_module(ctx.traffic["kind"]).run(ctx)
    section = "per_layer" if ctx.trace else "end_to_end"
    metrics = {}
    for m in spec.metrics_for(ctx.bench, ctx.workload["name"], section):
        value = spec.reader(m["name"])(out["run"])
        if value is None:
            # the cell lists this metric, so its reader must find it
            raise RuntimeError(f"metric {m['name']} read nothing in cell "
                               f"{ctx.workload['name']}")
        metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    lims = spec.limits(ctx.workload["name"])
    checks = {}
    for name, value in out["checks"].items():
        limit = lims.get(name, {}).get("limit")
        checks[name] = {"value": value, "limit": limit}
    correct = bool(checks) and all(
        c["limit"] is not None and math.isfinite(c["value"])
        and c["value"] <= c["limit"] for c in checks.values())
    result = {"correct": correct, "attempted": out["attempted"],
              "failed": out["failed"], "metrics": metrics,
              "window": out["window"]}
    summary = out["run"].trace
    if summary is not None:
        result["breakdown"] = summary.breakdown()
        result["trace"] = {"busy_s": summary.busy_s,
                           "window_s": summary.window_s}
    result["memory_peak_bytes"] = out["memory"]
    result["checks"] = checks
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    bench = spec.load_benchmark()
    wl = spec.workload(bench, args.workload)
    sys.path.insert(0, str(spec.ROOT / "src"))
    # the compile cache lives at a fixed path inside the checkout
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    import jax
    from repro.runtime.compile_cache import enable_persistent_cache

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < wl["chips"]:
        print(f"run.py: needs {wl['chips']} TPU chip(s); JAX found "
              f"{len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return 2
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    enable_persistent_cache()
    import peaks
    import traffic

    used = devices[:wl["chips"]]
    ctx = Ctx(bench=bench, workload=wl,
              cfile=spec.config_file(bench, wl["config"]),
              traffic=traffic.load(wl["traffic"]), seed=args.seed,
              seconds=args.seconds, trace=bool(args.trace),
              t_start=T_START, memory_peak=memory_peak(used),
              peak=peaks.peak(used[0].device_kind))
    res = run_cell(ctx)
    device = {"platform": used[0].platform, "kind": used[0].device_kind,
              "count": len(used),
              "memory_peak_bytes": res.pop("memory_peak_bytes")}
    tr = res.pop("trace", None)
    if tr is not None:
        device.update(tr)
    checks = res.pop("checks")
    print("window " + json.dumps(res.pop("window")), flush=True)
    line = {**res, "device": device, "checks": checks}
    for name, c in checks.items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
