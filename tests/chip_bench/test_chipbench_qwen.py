"""The qwen2.5-14b-8layer configuration and its serving cell: the file
describes the program's parameters at the published widths, its counts
are pinned by hand, the cell runs through ``run.run_cell`` at the smoke
sizes, and its own metric reads a trace."""
import types

import jax
import pytest

import clock
import flops
import peaks
import serve_cell
import spec
import weights
import xplane
from repro.models.init import abstract_params
from test_chipbench_harness import AlteredToken, checks_of, ctx_for

CONFIG = "qwen2.5-14b-8layer"
CELL = "qwen2.5-14b-8layer.serve.poisson"


@pytest.fixture(scope="module")
def cfile():
    return spec.config_file(spec.load_benchmark(), CONFIG)


def test_layout_matches_the_program_at_published_widths(cfile):
    cfg, fields = serve_cell.model_config(cfile, "serve", smoke=False)
    assert (cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim,
            cfg.d_ff, cfg.vocab_size) == (5120, 40, 8, 128, 13824, 152064)
    assert cfg.qkv_bias and not cfg.tie_embeddings
    assert cfg.num_layers == cfile["cut"]["num_hidden_layers"] == 8
    # shapes only: abstract parameters, no array is made
    abstract = abstract_params(cfg)
    weights.check_layout(fields, cfg.param_dtype, abstract)
    names = {".".join(str(getattr(k, "key", k)) for k in path)
             for path, _ in jax.tree_util.tree_flatten_with_path(abstract)[0]}
    assert {"blocks.attn.bq", "blocks.attn.bk", "blocks.attn.bv",
            "lm_head"} <= names


def test_matmul_params_of_the_cut_by_hand(cfile):
    per_layer = (5120 * 5120 + 2 * 5120 * 1024 + 5120 * 5120
                 + 3 * 5120 * 13824)
    assert per_layer == 275_251_200
    assert 5120 * 152064 == 778_567_680          # the untied head
    assert flops.matmul_params(cfile["model"]) == 2_980_577_280


def test_cell_is_correct_at_smoke_sizes_and_an_altered_token_is_not(
        monkeypatch):
    sound = checks_of(ctx_for(CELL), {}, monkeypatch)
    gap = sound["checks"]["served_logit_gap"]["value"]
    assert sound["attempted"] == 9 and sound["failed"] == 0
    assert sound["window"]["window_jit_compiles"] == 0
    limit = {"served_logit_gap": {"limit": max(4 * gap, 0.02)}}
    assert checks_of(ctx_for(CELL), limit, monkeypatch)["correct"]
    res = checks_of(ctx_for(CELL, make_executor=AlteredToken), limit,
                    monkeypatch)
    assert not res["correct"], res["checks"]


def _run(cfg, trace):
    calls = [clock.Call("decode", 0.0, 0.0, 0.01, rows=32, tokens=32_000,
                        cost=0.012, traced=True),
             clock.Call("decode", 0.0, 0.0, 0.01, rows=16, tokens=8_000,
                        cost=0.011, traced=True),
             clock.Call("decode", 0.0, 0.0, 0.01, rows=8, tokens=800,
                        cost=0.011, traced=False),
             clock.Call("prefill", 0.0, 0.0, 0.01, rows=1, tokens=1500,
                        cost=0.05, traced=True)]
    return types.SimpleNamespace(calls=calls, cfg=cfg, trace=trace,
                                 peak=peaks.peak("TPU v5 lite"))


def test_decode_hbm_roofline_reads_the_step_program(cfile):
    read = spec.reader("decode_hbm_roofline.serve")
    c = cfile["model"]
    summary = xplane.Summary(devices=1, window_s=1.0, busy_s=0.9,
                             programs={"jit__step": [2, 0.024],
                                       "jit_prefill": [1, 0.05]},
                             ops={}, idle_by_span={})
    # K and V of 8 kv heads of 128 in bf16 over 8 layers: 32,768 B a
    # position; each row's query and output, and its length
    kv, row = 32_768, 8 * (2 * 40 * 128 * 2 + 4)
    least = [(2 * 2_980_577_280 + kv * n + row * r) / 819e9
             for r, n in ((32, 32_000), (16, 8_000))]
    want = 100 * (sum(least) / 2) / 0.012
    assert read(_run(c, summary)) == pytest.approx(want, rel=1e-12)
    assert 50 < want < 100
    assert read(_run(c, None)) is None
    # a trace that holds no decode program reads nothing
    summary.programs = {"jit_prefill": [1, 0.05]}
    assert read(_run(c, summary)) is None
