"""The plain reference against the program's own forward pass, and the
benchmark's weights against the program's parameter layout."""
import dataclasses
import json
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import reference
import serve_cell
import weights
from repro.models import transformer
from repro.models.init import abstract_params

CONFIGS = pathlib.Path(__file__).resolve().parents[2] / "benchmarks" \
    / "chip" / "configs"


# the configuration's file, and the same with the two options the reference
# has that smollm-135m does not use: biases on Q/K/V and an untied head
VARIANTS = {"smollm-135m": {},
            "smollm-135m+qkv_bias+untied": {"qkv_bias": True,
                                            "tie_embeddings": False}}


def cfile_of(variant):
    cfile = json.loads((CONFIGS / "smollm-135m.json").read_text())
    cfile["model"].update(VARIANTS[variant])
    return cfile


def smoke(variant):
    cfg, fields = serve_cell.model_config(cfile_of(variant), "serve",
                                          smoke=True)
    cfg = dataclasses.replace(cfg, compute_dtype=jnp.float32,
                              param_dtype=jnp.float32)
    return cfg, dict(fields, compute_dtype="float32")


@pytest.mark.parametrize("name", list(VARIANTS))
def test_weights_have_the_program_layout_at_published_widths(name):
    cfg, fields = serve_cell.model_config(cfile_of(name), "serve",
                                          smoke=False)
    weights.check_layout(fields, cfg.param_dtype, abstract_params(cfg))
    bad = dict(fields, d_ff=fields["d_ff"] + 1)
    with pytest.raises(ValueError, match="layout differs"):
        weights.check_layout(bad, cfg.param_dtype, abstract_params(cfg))


def test_one_layer_made_alone_equals_that_layer_of_the_tree():
    _, f = smoke("smollm-135m+qkv_bias+untied")
    tree = weights.make(f, 2**40 + 3, jnp.bfloat16)
    one = weights.layer(f, 2**40 + 3, 1, jnp.bfloat16)
    np.testing.assert_array_equal(
        np.asarray(tree["blocks"]["attn"]["wq"][1], np.float32),
        np.asarray(one["attn.wq"]))
    np.testing.assert_array_equal(np.asarray(tree["blocks"]["ln2"][1],
                                             np.float32),
                                  np.asarray(one["ln2"]))
    other = weights.make(f, 2**40 + 4, jnp.bfloat16)
    assert not np.array_equal(np.asarray(tree["lm_head"], np.float32),
                              np.asarray(other["lm_head"], np.float32))


@pytest.mark.parametrize("name", list(VARIANTS))
def test_reference_matches_the_program_forward_in_float32(name):
    cfg, f = smoke(name)
    seed = 7
    params = weights.make(f, seed, jnp.float32)
    toks = np.random.default_rng(0).integers(0, f["vocab_size"], (2, 24),
                                             dtype=np.int32)
    with jax.default_matmul_precision("highest"):
        prog_loss, _ = transformer.loss_fn(params, {"tokens": toks}, cfg)
        prog_last, _ = transformer.prefill(params, {"tokens": toks}, cfg)
    (h,) = reference.hidden(f, seed, jnp.float32, toks, [False], rows=2)
    head = np.asarray(reference._head_w(f, seed, jnp.float32))
    logits = h.astype(np.float64) @ head
    lse = np.log(np.sum(np.exp(logits - logits.max(-1, keepdims=True)),
                        -1)) + logits.max(-1)
    gold = np.take_along_axis(logits[:, :-1], toks[:, 1:, None], -1)[..., 0]
    ref_loss = np.mean(lse[:, :-1] - gold)
    assert ref_loss == pytest.approx(float(prog_loss), rel=1e-5)
    np.testing.assert_allclose(logits[:, -1], np.asarray(prog_last),
                               rtol=1e-4, atol=1e-4)


def test_served_gaps_are_zero_for_the_references_own_greedy_tokens():
    _, f = smoke("smollm-135m")
    seed = 9
    prompt = np.random.default_rng(1).integers(0, f["vocab_size"], 10,
                                               dtype=np.int32)
    head = np.asarray(reference._head_w(f, seed, jnp.float32))
    seq = prompt
    for _ in range(6):                      # greedy from the reference
        (h,) = reference.hidden(f, seed, jnp.float32, seq[None], [False], 1)
        seq = np.append(seq, np.int32(np.argmax(h[0, -1] @ head)))
    served = seq[10:]
    g, _ = reference.served_gaps(f, seed, jnp.float32, [(prompt, served)],
                                 pad_to=32, rows=1, control=False)
    assert g.shape == (6,) and np.max(g) < 1e-5
    wrong = served.copy()
    wrong[2] = (wrong[2] + 1) % f["vocab_size"]
    g2, _ = reference.served_gaps(f, seed, jnp.float32, [(prompt, wrong)],
                                  pad_to=32, rows=1, control=False)
    assert g2[2] > 1e-3
