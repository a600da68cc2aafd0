"""Benchmark output snapshots: every ``results/fleet/*.json`` is validated
against a schema (required keys, value types, unit ranges), so a benchmark
refactor cannot silently change the output shape the paper-figure
artifacts — and anything downstream of them — depend on.

The schema language is deliberately tiny (no external deps): a spec is a
dict of key -> checker, where a checker is a type, a tuple of types, a
callable, or a nested spec dict.  ``goodput_row`` is the shared shape for
one SG/RG/PG/MPG composition.
"""
import json
import pathlib

import pytest

RESULTS = pathlib.Path(__file__).resolve().parents[1] / "results" / "fleet"
SERVE_RESULTS = RESULTS.parent / "serve"
REPO_ROOT = RESULTS.parents[1]


def unit(x):
    return isinstance(x, (int, float)) and 0.0 <= x <= 1.0


def positive(x):
    return isinstance(x, (int, float)) and x > 0


def non_negative(x):
    return isinstance(x, (int, float)) and x >= 0


def check(obj, spec, path=""):
    """Validate ``obj`` against ``spec``; returns a list of problems."""
    problems = []
    if isinstance(spec, dict):
        if not isinstance(obj, dict):
            return [f"{path}: expected dict, got {type(obj).__name__}"]
        for key, sub in spec.items():
            if key not in obj:
                problems.append(f"{path}.{key}: missing")
            else:
                problems += check(obj[key], sub, f"{path}.{key}")
    elif isinstance(spec, (type, tuple)):
        if not isinstance(obj, spec):
            problems.append(f"{path}: expected {spec}, "
                            f"got {type(obj).__name__}")
    elif callable(spec):
        if not spec(obj):
            problems.append(f"{path}: {spec.__name__} failed for {obj!r}")
    return problems


def each_value(spec):
    """Apply ``spec`` to every value of a (non-empty) dict."""
    def _each(obj):
        _each.problems = (
            [f"expected non-empty dict, got {type(obj).__name__}"]
            if not (isinstance(obj, dict) and obj) else
            [p for v in obj.values() for p in check(v, spec)])
        return not _each.problems
    _each.__name__ = f"each_value({getattr(spec, '__name__', spec)})"
    return _each


GOODPUT_ROW = {"SG": unit, "RG": unit, "PG": unit, "MPG": unit}

SCHEMAS = {
    "fig4_job_sizes.json": {
        "allocation_share_by_quarter":
            lambda x: isinstance(x, list) and len(x) >= 2
            and all(unit(v) for q in x for v in q.values()),
    },
    "fig12_pg_compiler.json": {
        "n_workloads": positive, "mean_pg_before": unit,
        "mean_pg_after": unit, "pg_uplift": positive,
        "workloads_improved": non_negative,
    },
    "fig14_rg_optimizations.json": {
        "rg_speedup_vs_baseline": each_value(positive),
        "baseline_rg": unit,
    },
    "fig15_rg_phases.json": {
        "rg_by_month": each_value(
            lambda x: isinstance(x, list) and all(unit(v) for v in x)),
    },
    "fig16_sg_by_size.json": {
        "sg_by_size": each_value(unit),
        "sg_overall": unit,
        "preemptions_by_size": each_value(non_negative),
        "policy_sweep": each_value({"sg_overall": unit}),
    },
    "ledger_scale.json": {
        "jobs": positive, "clusters": positive,
        "events_streamed": positive,
        "retained_state_entries": positive,
        "state_size": {"retained_intervals": lambda x: x == 0},
        # attribution rides the same stream without an interval list
        "attribution": {
            "state_entries": lambda x: 0 < x < 100,
            "conserved": lambda x: x is True,
            "lost_by_layer": each_value(non_negative),
        },
    },
    "table2_mpg_composition.json": {
        "table": each_value(GOODPUT_ROW),
        "checks": each_value(lambda x: isinstance(x, bool)),
    },
    "scenario_sweep.json": {
        "scale": str, "seed": int,
        "policies": each_value(
            {"placement": str, "preemption": str, "defrag": str}),
        "scenarios": each_value(each_value({
            **GOODPUT_ROW,
            "preemptions": non_negative, "xl_preemptions": non_negative,
            "failures": non_negative, "ledger_events": positive})),
        "checks": {
            # structural invariants must hold at any scale; directional
            # comparisons (maintenance_lowers_sg, ...) are recorded data
            "n_scenarios": lambda x: x >= 6,
            "n_policy_combos": lambda x: x >= 3,
            "all_bounded": lambda x: x is True,
            "protect_xl_never_evicts_xl": lambda x: x is True,
            "static_never_preempts": lambda x: x is True,
        },
    },
    "advisor_rank.json": {
        "scale": str,
        "knob_catalog": lambda x: isinstance(x, list) and len(x) >= 5,
        "scenarios": each_value({
            "baseline": GOODPUT_ROW,
            "conserved": lambda x: x is True,
            "lost_by_layer": each_value(non_negative),
            "ranking": lambda x: isinstance(x, list) and len(x) >= 5
            and all({"knob", "targets", "MPG", "recovered_mpg"} <= set(r)
                    for r in x),
        }),
        "checks": {
            # the PR acceptance matrix: >= 5 knobs on all 7 presets,
            # exact conservation everywhere, Fig 14 order on steady
            "n_scenarios": lambda x: x >= 7,
            "n_knobs": lambda x: x >= 5,
            "all_conserved": lambda x: x is True,
            "fig14_async_leads": lambda x: x is True,
            "policy_swap_noop_on_paper_baseline": lambda x: x is True,
            "gen_upgrade_pays_on_hetero": lambda x: x is True,
        },
    },
}


# paged KV allocator counters (repro.serve.kv_cache.KVCacheStats)
KV_CACHE = {
    "n_blocks": positive, "block_tokens": positive,
    "peak_blocks_used": non_negative, "allocations": non_negative,
    "block_appends": non_negative, "frees": non_negative,
    "failed_allocations": non_negative,
}


def kv_stats_or_none(x):
    """Continuous engines report allocator stats; static reserves
    per-slot dense caches and reports None."""
    return x is None or not check(x, KV_CACHE)


# one engine's metrics inside a serve_scale section (continuous/static)
SERVE_ENGINE_ROW = {
    "engine": str, "n_slots": positive, "requests": positive,
    "tokens": positive, "tokens_within_slo": non_negative,
    "slo_token_goodput": unit, "slo_goodput": unit,
    "preemptions": non_negative, "span": positive,
    "capacity_chip_time": positive,
    "goodput": GOODPUT_ROW,
    "ttft_s": {"mean": non_negative, "p50": non_negative,
               "p99": non_negative},
    "tpot_s": {"mean": non_negative, "p50": non_negative,
               "p99": non_negative},
    "rg_breakdown": each_value(unit),
    "kv_cache": kv_stats_or_none,
}

# every section of results/serve/serve_scale.json (and the committed
# BENCH_serve.json sections) is one equal-capacity A/B
SERVE_AB_SECTION = {
    "config": {"requests": positive, "span": positive, "n_slots": positive,
               "arrival": str, "slo_ttft": positive, "slo_tpot": positive,
               "seed": int},
    "config_fingerprint": str,
    "continuous": SERVE_ENGINE_ROW,
    "static": SERVE_ENGINE_ROW,
    # the PR acceptance invariant, shape-checked on every committed run:
    # continuous must beat static on tokens delivered within SLO
    "slo_tokens_margin": positive,
    "slo_token_goodput_margin": positive,
}

SERVE_SCHEMAS = {
    "serve_scale.json": each_value(SERVE_AB_SECTION),
}


def test_every_fleet_result_has_a_schema():
    files = sorted(p.name for p in RESULTS.glob("*.json"))
    assert files, f"no benchmark outputs under {RESULTS}"
    unschema = [f for f in files if f not in SCHEMAS]
    assert not unschema, (
        f"results/fleet file(s) without a schema: {unschema} — add one to "
        "tests/test_results_schema.py so refactors can't silently change "
        "their shape")


@pytest.mark.parametrize("name", sorted(SCHEMAS))
def test_fleet_result_matches_schema(name):
    path = RESULTS / name
    if not path.exists():
        pytest.skip(f"{name} not generated in this checkout")
    problems = check(json.loads(path.read_text()), SCHEMAS[name], name)
    assert not problems, "\n".join(problems)


def test_every_serve_result_has_a_schema():
    files = sorted(p.name for p in SERVE_RESULTS.glob("*.json")) \
        if SERVE_RESULTS.exists() else []
    unschema = [f for f in files if f not in SERVE_SCHEMAS]
    assert not unschema, (
        f"results/serve file(s) without a schema: {unschema} — add one to "
        "tests/test_results_schema.py so refactors can't silently change "
        "their shape")


@pytest.mark.parametrize("name", sorted(SERVE_SCHEMAS))
def test_serve_result_matches_schema(name):
    path = SERVE_RESULTS / name
    if not path.exists():
        pytest.skip(f"{name} not generated in this checkout")
    problems = check(json.loads(path.read_text()), SERVE_SCHEMAS[name], name)
    assert not problems, "\n".join(problems)


def test_committed_serve_bench_has_continuous_ahead():
    """PR acceptance: the committed BENCH_serve.json shows continuous
    beating static on within-SLO tokens at equal capacity, in every
    section."""
    path = REPO_ROOT / "BENCH_serve.json"
    if not path.exists():
        pytest.skip("BENCH_serve.json not committed in this checkout")
    bench = json.loads(path.read_text())
    sections = {k: v for k, v in bench.items()
                if isinstance(v, dict) and "slo_tokens_margin" in v}
    assert "tiny" in sections
    for name, section in sections.items():
        problems = check(section, SERVE_AB_SECTION, f"BENCH_serve.{name}")
        assert not problems, "\n".join(problems)
        c, s = section["continuous"], section["static"]
        assert c["n_slots"] == s["n_slots"]          # equal capacity
        assert c["tokens"] == s["tokens"]            # equal work
        assert c["tokens_within_slo"] > s["tokens_within_slo"], name


RESILIENCE_ARM = {
    **GOODPUT_ROW,
    "failures": int,
    "preemptions": int,
    "reshard_chip_time": non_negative,
    "gang_stall_chip_time": non_negative,
    "lost_by_layer": each_value(non_negative),
    "wall_s": non_negative,
}

RESILIENCE_PRESET = {
    "rigid": RESILIENCE_ARM,
    "elastic": RESILIENCE_ARM,
    # the PR acceptance invariant: elastic recovers MPG over rigid at
    # equal capacity, checked per committed section below
    "recovered_mpg": float,
    "recovered_by_layer": dict,
}

RESILIENCE_SECTION = {
    "config": {"n_jobs": positive, "seed": int, "n_pods": positive,
               "pod_size": positive, "horizon_days": positive,
               "slice_repair_s": positive, "target_load": positive},
    "config_fingerprint": str,
    "failure_storm": RESILIENCE_PRESET,
    "maintenance": RESILIENCE_PRESET,
}


def test_committed_resilience_bench_shows_elastic_recovery():
    """PR acceptance: the committed BENCH_resilience.json shows elastic
    recovering MPG over rigid on the failure_storm AND maintenance
    presets at equal capacity, in every section, with the loss moves
    attributed per layer; the tiny section also pins cross-engine
    equivalence under the repair window."""
    path = REPO_ROOT / "BENCH_resilience.json"
    if not path.exists():
        pytest.skip("BENCH_resilience.json not committed in this checkout")
    bench = json.loads(path.read_text())
    sections = {k: v for k, v in bench.items()
                if isinstance(v, dict) and "config_fingerprint" in v}
    assert "tiny" in sections
    for name, section in sections.items():
        problems = check(section, RESILIENCE_SECTION,
                         f"BENCH_resilience.{name}")
        assert not problems, "\n".join(problems)
        for preset in ("failure_storm", "maintenance"):
            p = section[preset]
            assert p["recovered_mpg"] > 0, (name, preset)
            assert p["recovered_mpg"] == pytest.approx(
                p["elastic"]["MPG"] - p["rigid"]["MPG"], abs=1e-6)
            # the mechanism, visible in the loss buckets: only the rigid
            # arm stalls surviving gang slices, only the elastic arm pays
            # reshard transfers
            assert p["elastic"]["reshard_chip_time"] > 0, (name, preset)
            assert p["elastic"]["gang_stall_chip_time"] == 0, (name, preset)
            assert p["rigid"]["reshard_chip_time"] == 0, (name, preset)
    assert bench["tiny"]["failure_storm"]["equivalence"]["engines_identical"]
    assert bench["tiny"]["maintenance"]["equivalence"]["engines_identical"]
    # the advisor section ranks the resiliency knobs on the same preset
    adv = bench.get("advisor")
    if adv:
        assert {r["knob"] for r in adv["ranking"]} == \
            {"elastic_resize", "multi_slice_gang"}


CONTROLLER_ARM = {
    **GOODPUT_ROW,
    "failures": int,
    "lost_by_layer": each_value(non_negative),
    "wall_s": non_negative,
}

CONTROLLER_PRESET = {
    "rigid": CONTROLLER_ARM,
    "elastic": CONTROLLER_ARM,
    "controlled": {**CONTROLLER_ARM,
                   "switches": list,
                   "policy_switch_chip_time": non_negative},
    "oracle_static": lambda x: x in ("rigid", "elastic"),
    "best_static_mpg": unit,
    "regret_mpg": float,
    "recovered_by_layer": dict,
}

CONTROLLER_SECTION = {
    "config": {"n_jobs": positive, "seed": int, "n_pods": positive,
               "pod_size": positive, "horizon_days": positive,
               "slice_repair_s": positive, "target_load": positive},
    "config_fingerprint": str,
    "summary": {
        "avg_mpg": {"rigid": unit, "elastic": unit, "controlled": unit},
        "best_static_arm": lambda x: x in ("rigid", "elastic"),
        "controller_beats_best_static_avg": lambda x: isinstance(x, bool),
        "max_regret_mpg": float,
    },
}

ADVERSARIAL_ROW = {
    "name": str,
    "genome": dict,
    "controlled_mpg": unit,
    "rigid_mpg": unit,
    "elastic_mpg": unit,
    "best_static_mpg": unit,
    "controller_survives": lambda x: isinstance(x, bool),
    "n_switches": non_negative,
}


def test_committed_controller_bench_passes_the_acceptance_gates():
    """PR acceptance on the committed BENCH_controller.json: (a) regret
    vs the per-preset best static policy <= 5% MPG on all 7 presets in
    every section, (b) the controlled average strictly above the best
    single static arm's average, and (c) the controller surviving every
    adversarially-searched scenario at or above the best static's MPG —
    with switch overhead attributed and cross-engine equivalence pinned
    in the tiny section."""
    path = REPO_ROOT / "BENCH_controller.json"
    if not path.exists():
        pytest.skip("BENCH_controller.json not committed in this checkout")
    bench = json.loads(path.read_text())
    sections = {k: v for k, v in bench.items()
                if isinstance(v, dict) and "summary" in v}
    assert "tiny" in sections
    presets = ("steady", "diurnal", "bursty", "maintenance",
               "failure_storm", "hetero_fleet", "peak_week")
    for name, section in sections.items():
        problems = check(section, CONTROLLER_SECTION,
                         f"BENCH_controller.{name}")
        for preset in presets:
            problems += check(section[preset], CONTROLLER_PRESET,
                              f"BENCH_controller.{name}.{preset}")
        assert not problems, "\n".join(problems)
        for preset in presets:
            p = section[preset]
            # gate (a): bounded regret vs the per-scenario oracle
            assert p["regret_mpg"] <= 0.05, (name, preset)
            assert p["best_static_mpg"] == \
                max(p["rigid"]["MPG"], p["elastic"]["MPG"])
        # gate (b): adapting beats committing to one static policy
        summary = section["summary"]
        assert summary["controller_beats_best_static_avg"] is True, name
        best = summary["avg_mpg"][summary["best_static_arm"]]
        assert summary["avg_mpg"]["controlled"] > best, name
    # controlled runs are bit-identical across engines (tiny section)
    for preset in presets:
        assert bench["tiny"][preset]["equivalence"]["engines_identical"]
    # gate (c): the committed adversarial suite never drives the
    # controller below the best static floor
    adv = bench["adversarial"]
    assert len(adv["suite"]) >= 3
    for row in adv["suite"]:
        problems = check(row, ADVERSARIAL_ROW,
                         f"BENCH_controller.adversarial.{row.get('name')}")
        assert not problems, "\n".join(problems)
        assert row["controller_survives"] is True, row["name"]
        assert row["controlled_mpg"] >= row["best_static_mpg"], row["name"]
        assert row["best_static_mpg"] == \
            max(row["rigid_mpg"], row["elastic_mpg"])


def test_scenario_sweep_covers_the_acceptance_matrix():
    """PR acceptance: >= 6 scenarios x 3 policy combos in the artifact."""
    path = RESULTS / "scenario_sweep.json"
    if not path.exists():
        pytest.skip("scenario_sweep.json not generated in this checkout")
    d = json.loads(path.read_text())
    assert len(d["scenarios"]) >= 6
    assert all(len(by_policy) >= 3 for by_policy in d["scenarios"].values())
