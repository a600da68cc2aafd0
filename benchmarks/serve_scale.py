"""Serve-scale benchmark: continuous vs static batching at equal capacity
(``BENCH_serve.json`` + ``results/serve/serve_scale.json``).

Drives :class:`repro.serve.ContinuousServeEngine` and its equal-capacity
static reference (``repro.serve.run_static``) with the *simulated*
executor over scenario arrival processes
(``repro.fleet.scenarios.request_arrivals`` — the same diurnal/bursty
modulations the fleet simulator uses), so the whole run is virtual-time,
deterministic, and numpy-free-importable for the benchmark CI jobs.

Sections:

  * ``tiny`` — seconds-long bursty run under BOTH engines; CI runs only
    this (``--tiny``) and ``--check`` gates on the ordering invariant
    (continuous delivers MORE tokens within SLO than static at equal
    capacity) plus a regression floor on the continuous engine's
    SLO-token-goodput margin vs the committed baseline;
  * ``diurnal`` / ``bursty`` — large-request-count runs (the paper's
    fluctuating-demand serving story, Fig. 15): p50/p99 TTFT and
    per-token latency alongside SG/RG/PG and SLO-goodput for both
    engines.

Every section records a config fingerprint so numbers are never compared
across silently different workloads.
"""
from __future__ import annotations

import argparse
import hashlib
import json
import pathlib
import time
from typing import Dict

from repro.fleet.scenarios import SCENARIOS, request_arrivals
from repro.serve import (ContinuousServeEngine, ServeSLO, SimulatedExecutor,
                         run_static, synthetic_requests)

from benchmarks.common import save_json

REPO_ROOT = pathlib.Path(__file__).resolve().parents[1]
BENCH_PATH = REPO_ROOT / "BENCH_serve.json"

# CI regression gate: fail when the fresh tiny-section SLO-token-goodput
# *margin* (continuous - static) drops below this fraction of the
# committed baseline's margin
REGRESSION_FLOOR = 0.5

TINY = {"requests": 400, "span": 120.0, "n_slots": 4, "arrival": "bursty",
        "prompt_len": 96, "max_new": [8, 48], "slo_ttft": 1.0,
        "slo_tpot": 0.05, "seed": 42}
# ~16 slots x ~670 tok/s serving ~800k tokens over 25 virtual minutes:
# load ~0.8, where scheduling policy is what separates the engines
FULL = {"requests": 20_000, "span": 1500.0, "n_slots": 16,
        "prompt_len": 128, "max_new": [16, 64], "slo_ttft": 1.0,
        "slo_tpot": 0.05, "seed": 42}
# same load point at 1/10 the population for `benchmarks.run` quick mode
QUICK = dict(FULL, requests=2_000, span=150.0)


def _fingerprint(cfg: Dict) -> str:
    return hashlib.sha256(
        json.dumps(cfg, sort_keys=True).encode()).hexdigest()[:16]


def _requests(cfg: Dict, arrival: str):
    arr = request_arrivals(cfg["requests"], cfg["span"], seed=cfg["seed"],
                           arrival=SCENARIOS[arrival].arrival)
    return synthetic_requests(arr, prompt_len=cfg["prompt_len"],
                              max_new=tuple(cfg["max_new"]),
                              seed=cfg["seed"])


def _engine_dict(report, wall_s: float) -> Dict:
    out = report.as_dict()
    out["bench_wall_s"] = round(wall_s, 3)
    out["tokens_per_virtual_s"] = (round(report.tokens / report.span, 1)
                                   if report.span else 0.0)
    return out


def run_section(cfg: Dict, arrival: str) -> Dict:
    """Both engines over the identical request stream and SLO."""
    slo = ServeSLO(ttft=cfg["slo_ttft"], tpot=cfg["slo_tpot"])
    t0 = time.perf_counter()
    cont = ContinuousServeEngine(cfg["n_slots"], SimulatedExecutor(),
                                 slo=slo).run(_requests(cfg, arrival))
    wall_c = time.perf_counter() - t0
    t0 = time.perf_counter()
    stat = run_static(_requests(cfg, arrival), batch=cfg["n_slots"],
                      executor=SimulatedExecutor(), slo=slo)
    wall_s = time.perf_counter() - t0
    assert cont.tokens == stat.tokens, "engines must deliver equal work"
    full_cfg = dict(cfg, arrival=arrival)
    return {
        "config": full_cfg,
        "config_fingerprint": _fingerprint(full_cfg),
        "continuous": _engine_dict(cont, wall_c),
        "static": _engine_dict(stat, wall_s),
        "slo_tokens_margin": cont.tokens_within_slo - stat.tokens_within_slo,
        "slo_token_goodput_margin": round(
            cont.slo_token_goodput - stat.slo_token_goodput, 6),
    }


def _load_committed() -> Dict:
    if BENCH_PATH.exists():
        return json.loads(BENCH_PATH.read_text())
    return {}


def check(fresh_tiny: Dict, committed: Dict) -> None:
    """CI gate: (1) the ordering invariant — continuous must beat static
    on tokens delivered within SLO at equal capacity; (2) the margin must
    not collapse vs the committed baseline."""
    margin = fresh_tiny["slo_tokens_margin"]
    if margin <= 0:
        raise SystemExit(
            f"serve_scale --check FAILED: continuous does not beat static "
            f"on within-SLO tokens (margin {margin})")
    base = committed.get("tiny")
    if not base:
        print("serve_scale --check: no committed baseline; ordering "
              "invariant OK, skipping margin gate")
        return
    if base.get("config_fingerprint") != fresh_tiny["config_fingerprint"]:
        print("serve_scale --check: tiny config changed; committed "
              "baseline not comparable — skipping margin gate (commit a "
              "fresh BENCH_serve.json)")
        return
    floor = base["slo_token_goodput_margin"] * REGRESSION_FLOOR
    fresh = fresh_tiny["slo_token_goodput_margin"]
    msg = (f"tiny SLO-goodput margin {fresh:.4f} vs committed "
           f"{base['slo_token_goodput_margin']:.4f} (floor {floor:.4f})")
    if fresh < floor:
        raise SystemExit(f"serve_scale --check FAILED: {msg}")
    print(f"serve_scale --check OK: {msg}")


def main(quick: bool = False, tiny: bool = False,
         do_check: bool = False) -> Dict:
    committed = _load_committed()
    bench = dict(committed)
    t_start = time.monotonic()
    fresh_tiny = run_section(TINY, TINY["arrival"])
    bench["tiny"] = fresh_tiny
    sections = {"tiny": fresh_tiny}
    if do_check:
        check(fresh_tiny, committed)
    if not tiny:
        cfg = QUICK if quick else FULL
        for arrival in ("diurnal", "bursty"):
            sections[arrival] = bench[arrival] = run_section(cfg, arrival)
    bench["version"] = 1
    bench["generated_by"] = "benchmarks/serve_scale.py"
    BENCH_PATH.write_text(json.dumps(bench, indent=1, sort_keys=True) + "\n")
    save_json("serve/serve_scale.json", sections)
    wall_us = (time.monotonic() - t_start) * 1e6
    derived = {
        "tiny_slo_margin": fresh_tiny["slo_tokens_margin"],
        "tiny_continuous_slo_goodput":
            fresh_tiny["continuous"]["slo_token_goodput"],
    }
    if "bursty" in sections:
        derived["bursty_slo_margin"] = \
            sections["bursty"]["slo_tokens_margin"]
        derived["bursty_p99_ttft_continuous"] = \
            sections["bursty"]["continuous"]["ttft_s"]["p99"]
    print(f"serve_scale,{wall_us:.1f},{json.dumps(derived, sort_keys=True)}")
    return bench


if __name__ == "__main__":
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--tiny", action="store_true",
                    help="CI smoke: only the tiny A/B section")
    ap.add_argument("--full", action="store_true",
                    help="paper-scale request populations (slower)")
    ap.add_argument("--check", action="store_true",
                    help="fail if continuous stops beating static on "
                         "within-SLO tokens, or the margin regressed vs "
                         "the committed BENCH_serve.json")
    args = ap.parse_args()
    main(quick=not args.full, tiny=args.tiny, do_check=args.check)
