"""The harness end to end on the CPU at the configurations' smoke sizes,
through ``run.run_cell`` (which skips only the look for a chip): a sound
run is correct, and a run with the timed path broken underneath is not.

The limits here are set for the smoke sizes, from the sound run of the
same test; the cells' own limits are for their published widths."""
import json
import os
import shutil
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import pytest

import family
import run
import spec
import traffic
from repro.models import model
from repro.serve.batched_executor import JaxBatchedExecutor

SERVE = "smollm-135m.serve.poisson"


def ctx_for(cell, **kw):
    bench = spec.load_benchmark()
    wl = spec.workload(bench, cell)
    cfile = spec.config_file(bench, wl["config"])
    cfile["serve"]["slots"] = 4
    mix = traffic.load(wl["traffic"])
    mix.update(prompt_menu=[24, 40], rate_per_s=6.0, tail_s=0.5,
               output_lognormal={"median": 8, "sigma": 0.5, "min": 4,
                                 "max": 12})
    return run.Ctx(bench=bench, workload=wl, cfile=cfile, traffic=mix,
                   seed=2**31 + 21, seconds=1.5, trace=False,
                   t_start=time.monotonic(), smoke=True, **kw)


def checks_of(ctx, limits, monkeypatch):
    monkeypatch.setattr(spec, "limits", lambda cell: limits)
    return run.run_cell(ctx)


class AlteredToken(JaxBatchedExecutor):
    """Alters one served token where decode produces it."""

    def decode(self, reqs):
        toks, cost = super().decode(reqs)
        toks[0] = (toks[0] + 1) % self.cfg.vocab_size
        return toks, cost


class HalfBatch(JaxBatchedExecutor):
    """A decode step that leaves out the later half of its rows (a lone
    row too): they run as inactive rows (no length, no pages), so their
    position is never written and their token is computed from no
    context."""

    def decode(self, reqs):
        keep = len(reqs) // 2
        for r in reqs[keep:]:
            row = self.rows[r.rid]
            self._len[row] = 0
            self._tables[row, :] = self.null_page
        toks, cost = super().decode(reqs[:keep])
        return toks + [int(self._tok[self.rows[r.rid]])
                       for r in reqs[keep:]], cost


class StaleCache(JaxBatchedExecutor):
    """A decode step that returns the KV pages unchanged."""

    def __init__(self, cfg, max_len, n_slots):
        super().__init__(cfg, max_len, n_slots)
        step = model.paged_decode_fn(cfg)

        def _step(p, tok, lens, kp, vp, bt):
            logits, _, _ = step(p, tok, lens, kp, vp, bt)
            return jnp.argmax(logits, -1).astype(jnp.int32), kp, vp

        self._decode = jax.jit(_step)


def test_serve_sound_run_is_correct_and_faults_are_not(monkeypatch):
    sound = checks_of(ctx_for(SERVE), {}, monkeypatch)
    gap = sound["checks"]["served_logit_gap"]["value"]
    assert sound["attempted"] == 9 and sound["failed"] == 0
    # what run.py prints of it is plain JSON
    json.dumps(sound["window"], allow_nan=False)
    json.dumps({k: sound[k] for k in ("correct", "attempted", "failed",
                                      "metrics", "checks")},
               allow_nan=False)
    assert sound["window"]["window_jit_compiles"] == 0
    assert set(sound["metrics"]) == {"ttft_p95_s", "itl_p95_ms",
                                     "output_tokens_per_s", "setup_s"}
    limit = {"served_logit_gap": {"limit": max(4 * gap, 0.02)}}
    assert checks_of(ctx_for(SERVE), limit, monkeypatch)["correct"]
    for broken in (AlteredToken, HalfBatch, StaleCache):
        res = checks_of(ctx_for(SERVE, make_executor=broken), limit,
                        monkeypatch)
        assert not res["correct"], (broken.__name__, res["checks"])
    # the fp8 control in the program's place is not correct either
    res = checks_of(ctx_for(SERVE, control=True), limit, monkeypatch)
    assert not res["correct"], res["checks"]


def test_a_family_with_no_module_fails_at_set_up():
    ctx = ctx_for(SERVE)
    ctx.cfile["model"]["family"] = "no-such-family"
    with pytest.raises(FileNotFoundError,
                       match=r"families/no-such-family\.py"):
        run.run_cell(ctx)


# appended to a copy of families/dense.py: its reference layer, off by a
# constant
OFF_BY_A_CONSTANT = """

_exact_block = block


def block(x, w, c, quant):
    return _exact_block(x, w, c, quant) + 1.0
"""


def test_the_check_reads_the_reference_layer_from_the_family_file(
        monkeypatch, tmp_path):
    sound = checks_of(ctx_for(SERVE), {}, monkeypatch)
    gap = sound["checks"]["served_logit_gap"]["value"]
    limit = {"served_logit_gap": {"limit": max(4 * gap, 0.02)}}
    (tmp_path / "dense.py").write_text(
        (family.DIR / "dense.py").read_text() + OFF_BY_A_CONSTANT)
    monkeypatch.setattr(family, "DIR", tmp_path)
    off = checks_of(ctx_for(SERVE), limit, monkeypatch)
    assert (off["attempted"], off["failed"]) == (sound["attempted"], 0)
    assert not off["correct"], off["checks"]


def test_run_refuses_a_host_without_a_tpu(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    root = spec.ROOT
    cmd = [sys.executable, "benchmarks/chip/run.py", "--workload", SERVE,
           "--seed", "1", "--seconds", "1", "--trace", "0"]
    p = subprocess.run(cmd, cwd=root, env=env, capture_output=True,
                       text=True, timeout=120)
    assert p.returncode != 0 and p.stdout.strip() == ""
    assert "TPU" in p.stderr
    # a directory with only BENCHMARK.json and the benchmark's paths
    shutil.copy(root / "BENCHMARK.json", tmp_path)
    for d in ("benchmarks/chip", "tests/chip_bench"):
        shutil.copytree(root / d, tmp_path / d,
                        ignore=shutil.ignore_patterns("__pycache__"))
    p = subprocess.run(cmd, cwd=tmp_path, env=env, capture_output=True,
                       text=True, timeout=120)
    assert p.returncode != 0 and p.stdout.strip() == ""
