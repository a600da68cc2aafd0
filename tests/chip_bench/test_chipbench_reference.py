"""The plain reference against the program's own forward pass, and the
benchmark's weights against the program's parameter layout."""
import dataclasses
import hashlib
import json
import pathlib
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import family
import reference
import serve_cell
import weights
from family import Leaf
from repro.models import transformer
from repro.models.config import ModelConfig
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


# sha256 of smollm-135m's smoke-size weights for seed 2**40 + 15: a change
# to any leaf's name, shape, dtype, init or key moves it
SMOKE_DIGESTS = {
    "bfloat16":
        "4842e6adbed73ed03a56a09cc6599501bcd8a959d75a0066091e371d0717013d",
    "float32":
        "4e0d614dff060ff3d4b4feedde5833b65b6621c8acf7868a3e864f0e6a8e2675",
}


@pytest.mark.parametrize("dtype", list(SMOKE_DIGESTS))
def test_smoke_weights_of_a_fixed_seed_keep_their_digest(dtype):
    _, f = serve_cell.model_config(cfile_of("smollm-135m"), "serve",
                                   smoke=True)
    tree = weights.make(f, 2**40 + 15, jnp.dtype(dtype))
    h = hashlib.sha256()
    leaves = jax.tree_util.tree_flatten_with_path(tree)[0]
    for path, leaf in sorted(leaves,
                             key=lambda t: jax.tree_util.keystr(t[0])):
        a = np.asarray(leaf)
        h.update(jax.tree_util.keystr(path).encode())
        h.update(str(a.dtype).encode())
        h.update(repr(a.shape).encode())
        h.update(a.tobytes())
    assert h.hexdigest() == SMOKE_DIGESTS[dtype]


# A deepseek-style tree: one unstacked dense layer first, then a stack of
# num_layers - first_k_dense layers of routed and shared experts.
MOE = {"name": "moe-tiny", "family": "moe", "num_layers": 3, "d_model": 32,
       "num_heads": 4, "num_kv_heads": 2, "head_dim": 8, "d_ff": 16,
       "vocab_size": 64, "tie_embeddings": False, "first_k_dense": 1,
       "num_experts": 4, "experts_per_token": 2, "num_shared_experts": 1}


def moe_layout(c):
    d, ff, V, E = c["d_model"], c["d_ff"], c["vocab_size"], c["num_experts"]
    q = c["num_heads"] * c["head_dim"]
    kv = c["num_kv_heads"] * c["head_dim"]
    sff = c["num_shared_experts"] * ff
    k = c["first_k_dense"]
    common = [("ln1", (d,)), ("ln2", (d,)), ("attn.wq", (d, q)),
              ("attn.wk", (d, kv)), ("attn.wv", (d, kv)), ("attn.wo", (q, d))]
    mlp = [("mlp.wi", (d, ff)), ("mlp.wg", (d, ff)), ("mlp.wo", (ff, d))]
    moe = [("moe.router", (d, E)), ("moe.experts.wi", (E, d, ff)),
           ("moe.experts.wg", (E, d, ff)), ("moe.experts.wo", (E, ff, d)),
           ("moe.shared.wi", (d, sff)), ("moe.shared.wg", (d, sff)),
           ("moe.shared.wo", (sff, d))]

    def init(shape):
        return "norm" if len(shape) == 1 else "fan_in"

    leaves = {"embed.tok": Leaf((V, d), True, "embed", 0),
              "final_norm": Leaf((d,), False, "norm", 0),
              "lm_head": Leaf((d, V), True, "fan_in", 0)}
    for i in range(k):
        for name, shape in common + mlp:
            leaves[f"dense_layers.{i}.{name}"] = Leaf(
                shape, len(shape) >= 2, init(shape), 0)
    for name, shape in common + moe:
        leaves[f"blocks.{name}"] = Leaf(shape, True, init(shape),
                                        c["num_layers"] - k)
    return leaves


def moe_layer_leaves(c, i):
    k = c["first_k_dense"]
    pre = f"dense_layers.{i}." if i < k else "blocks."
    return {name[len(pre):]: (name, None if i < k else i - k)
            for name in moe_layout(c) if name.startswith(pre)}


def test_a_two_stack_layout_makes_the_programs_tree(monkeypatch):
    fam = types.ModuleType("moe_layout")
    fam.layout, fam.layer_leaves = moe_layout, moe_layer_leaves
    monkeypatch.setattr(family, "load", lambda name: fam)
    cfg = ModelConfig(**MOE, param_dtype=jnp.bfloat16)
    abstract = abstract_params(cfg)
    weights.check_layout(MOE, jnp.bfloat16, abstract)
    with pytest.raises(ValueError, match="layout differs"):
        weights.check_layout(MOE, jnp.bfloat16, abstract_params(
            dataclasses.replace(cfg, num_shared_experts=0)))
    seed = 2**36 + 5
    tree = weights.make(MOE, seed, jnp.bfloat16)
    assert jax.tree.structure(tree) == jax.tree.structure(abstract)
    assert jax.tree.leaves(jax.tree.map(
        lambda a, b: (a.shape, a.dtype) == (b.shape, b.dtype),
        tree, abstract)) == [True] * len(jax.tree.leaves(abstract))
    # layer 0 is the unstacked dense layer, layer 2 entry 1 of the stack
    first = weights.layer(MOE, seed, 0, jnp.bfloat16)
    assert "mlp.wi" in first and "moe.router" not in first
    np.testing.assert_array_equal(
        np.asarray(tree["dense_layers"]["0"]["mlp"]["wi"], np.float32),
        np.asarray(first["mlp.wi"]))
    np.testing.assert_array_equal(
        np.asarray(tree["dense_layers"]["0"]["ln1"]),
        np.asarray(first["ln1"]))
    last = weights.layer(MOE, seed, 2, jnp.bfloat16)
    np.testing.assert_array_equal(
        np.asarray(tree["blocks"]["moe"]["experts"]["wo"][1], np.float32),
        np.asarray(last["moe.experts.wo"]))
    np.testing.assert_array_equal(
        np.asarray(tree["blocks"]["ln2"][1], np.float32),
        np.asarray(last["ln2"]))


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
