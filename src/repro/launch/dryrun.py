import os
# 512 placeholder host devices, pinned to the CPU backend so a dry-run on a
# machine with a chip neither takes the chip nor builds its mesh from it
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=512").strip()

"""Multi-pod dry-run (deliverable e).

For every (architecture x input-shape x mesh) cell: build the production
mesh from placeholder host devices, lower + compile the step function with
its real shardings, and record memory_analysis / cost_analysis / collective
traffic.  No arrays are ever allocated — everything is abstract.

    PYTHONPATH=src python -m repro.launch.dryrun --arch mixtral-8x7b --shape train_4k
    PYTHONPATH=src python -m repro.launch.dryrun --all            # 33 cells x 2 meshes
"""
import argparse
import json
import pathlib
import time
import traceback

import jax

from repro.configs import ARCH_IDS, get_config
from repro.core import hlo_analysis
from repro.core.hardware import TPU_V5E
from repro.launch.mesh import make_production_mesh
from repro.launch.strategy import lower_cell
from repro.models.config import SHAPES, SHAPES_BY_NAME, shape_applicable

RESULTS_DIR = pathlib.Path(__file__).resolve().parents[3] / "results" / "dryrun"


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             save: bool = True, rules=None, cfg_override=None,
             variant: str = "baseline") -> dict:
    cfg = cfg_override or get_config(arch)
    if variant != "baseline":
        from repro.launch.variants import apply_variant

        cfg = apply_variant(cfg, variant)
    shape = SHAPES_BY_NAME[shape_name]
    ok, why = shape_applicable(cfg, shape)
    if not ok:
        return {"arch": arch, "shape": shape_name, "skipped": why}

    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = mesh.devices.size
    t0 = time.time()
    lowered = lower_cell(cfg, shape, mesh, rules=rules)
    t1 = time.time()
    compiled = lowered.compile()
    t2 = time.time()

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    coll = hlo_analysis.collective_stats(compiled.as_text())

    top = hlo_analysis.top_collectives(compiled.as_text(), 8)
    rec = {
        "arch": arch,
        "shape": shape_name,
        "variant": variant,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "chips": chips,
        "lower_s": round(t1 - t0, 2),
        "compile_s": round(t2 - t1, 2),
        "memory": {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
            "generated_code_bytes": getattr(mem, "generated_code_size_in_bytes", None),
            "peak_bytes": (getattr(mem, "argument_size_in_bytes", 0) or 0)
            + (getattr(mem, "temp_size_in_bytes", 0) or 0),
            "hbm_per_chip": TPU_V5E.hbm_bytes,
        },
        "cost": {
            "flops_once": cost.get("flops"),
            "bytes_once": cost.get("bytes accessed"),
        },
        "collectives": {
            "bytes_by_kind": coll.bytes_by_kind,
            "count_by_kind": coll.count_by_kind,
            "total_bytes": coll.total_bytes,
        },
        "while_trips": hlo_analysis.while_trip_counts(compiled.as_text())[:20],
        "top_collectives": top,
    }
    if save:
        RESULTS_DIR.mkdir(parents=True, exist_ok=True)
        suffix = "" if variant == "baseline" else f"__{variant}"
        name = f"{arch}__{shape_name}__{rec['mesh']}{suffix}.json"
        (RESULTS_DIR / name).write_text(json.dumps(rec, indent=1))
    return rec


def fits(rec) -> bool:
    m = rec.get("memory", {})
    peak = (m.get("argument_bytes") or 0) + (m.get("temp_bytes") or 0)
    return peak <= TPU_V5E.hbm_bytes


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--single-pod-only", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--variant", default="baseline")
    args = ap.parse_args()

    archs = [args.arch] if args.arch else list(ARCH_IDS)
    shapes = [args.shape] if args.shape else [s.name for s in SHAPES]
    meshes = [False, True]
    if args.multi_pod:
        meshes = [True]
    if args.single_pod_only:
        meshes = [False]

    n_ok = n_skip = n_fail = 0
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                tag = f"{arch:24s} {shape:12s} {'2x16x16' if mp else '16x16 '}"
                try:
                    rec = run_cell(arch, shape, mp, variant=args.variant)
                except Exception as e:  # noqa: BLE001 — report, keep sweeping
                    n_fail += 1
                    print(f"FAIL {tag}: {type(e).__name__}: {e}")
                    traceback.print_exc(limit=3)
                    continue
                if "skipped" in rec:
                    n_skip += 1
                    print(f"SKIP {tag}: {rec['skipped']}")
                    continue
                n_ok += 1
                m = rec["memory"]
                peak = ((m["argument_bytes"] or 0) + (m["temp_bytes"] or 0)) / 2**30
                print(f"OK   {tag}: compile={rec['compile_s']:7.1f}s "
                      f"peak/chip={peak:6.2f}GiB "
                      f"coll={rec['collectives']['total_bytes']/2**30:8.2f}GiB "
                      f"{'FITS' if fits(rec) else 'OVER-HBM'}")
    print(f"\ndry-run: {n_ok} ok, {n_skip} skipped, {n_fail} failed")
    raise SystemExit(1 if n_fail else 0)


if __name__ == "__main__":
    main()
