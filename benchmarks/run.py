"""Benchmark harness entry point (deliverable d).

One function per paper table/figure; prints ``name,us_per_call,derived``
CSV rows.  ``python -m benchmarks.run`` runs the fleet-scale benches in
quick mode; pass --full for the paper-scale populations and the roofline
table (requires the dry-run artifacts; see repro.launch.dryrun).
"""
from __future__ import annotations

import argparse
import cProfile
import pstats
import sys
import traceback

from benchmarks import (advisor_rank, fig4_job_sizes, fig12_pg_compiler,
                        fig14_rg_optimizations, fig15_rg_phases,
                        fig16_sg_by_size, fleet_scale, ledger_scale,
                        overlap_speedup, roofline, scenario_sweep,
                        serve_scale, table2_mpg_composition)
from benchmarks.common import RESULTS

BENCHES = [
    ("fig4_job_sizes", fig4_job_sizes.main),
    ("fig12_pg_compiler", fig12_pg_compiler.main),
    ("fig14_rg_optimizations", fig14_rg_optimizations.main),
    ("fig15_rg_phases", fig15_rg_phases.main),
    ("fig16_sg_by_size", fig16_sg_by_size.main),
    ("table2_mpg_composition", table2_mpg_composition.main),
    ("ledger_scale", ledger_scale.main),
    ("fleet_scale", fleet_scale.main),
    ("serve_scale", serve_scale.main),
    ("scenario_sweep", scenario_sweep.main),
    ("advisor_rank", advisor_rank.main),
    ("overlap_speedup", overlap_speedup.main),
    ("roofline_table", roofline.main),
]


def _run_profiled(name: str, fn, quick: bool) -> None:
    """cProfile one bench into results/profiles/<name>.pstats and print
    the top-25 cumulative entries, so hot-path regressions are
    diagnosable straight from a CI artifact."""
    prof_dir = RESULTS / "profiles"
    prof_dir.mkdir(parents=True, exist_ok=True)
    out = prof_dir / f"{name}.pstats"
    prof = cProfile.Profile()
    prof.enable()
    try:
        fn(quick=quick)
    finally:
        prof.disable()
        prof.dump_stats(out)
        print(f"# profile written: {out}", file=sys.stderr)
        stats = pstats.Stats(prof, stream=sys.stderr)
        stats.sort_stats("cumulative").print_stats(25)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="paper-scale populations (slower)")
    ap.add_argument("--only", default=None)
    ap.add_argument("--profile", action="store_true",
                    help="cProfile each bench into results/profiles/"
                         "<bench>.pstats and print the top-25 cumulative")
    args = ap.parse_args()

    print("name,us_per_call,derived")
    failures = 0
    for name, fn in BENCHES:
        if args.only and args.only not in name:
            continue
        try:
            if args.profile:
                _run_profiled(name, fn, quick=not args.full)
            else:
                fn(quick=not args.full)
        except Exception as e:  # noqa: BLE001 - report and continue
            failures += 1
            print(f'{name},-1,"ERROR: {type(e).__name__}: {e}"')
            traceback.print_exc(limit=2, file=sys.stderr)
    if failures:
        raise SystemExit(1)


if __name__ == '__main__':
    main()
