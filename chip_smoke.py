"""Drive the serve and train paths once on a TPU, at smollm-135m's full
published width (30 layers, d_model 576, vocab 49152, bf16).

    python3 chip_smoke.py [--seed N]               # one chip
    python3 chip_smoke.py --four-chips [--seed N]  # four chips

One chip runs, in one process and in this order:

  * device — exits nonzero, and prints no result, unless the first JAX
    device is a TPU;
  * kernel vs ref — the paged-attention Pallas kernel against the XLA
    gather reference on the same bf16 inputs;
  * serve — ``repro.launch.serve.run_continuous_server``, which picks the
    batched paged-decode executor for this family, then the decode
    program's kernel, its compile count, and one decode step's logits
    under the kernel vs the reference;
  * train — ``repro.runtime.orchestrator.Orchestrator``, as
    ``repro.launch.train`` builds it, for a few steps and one checkpoint.

``--four-chips`` runs only the sharded train step
(``repro.launch.strategy.jit_train_step`` on a 2x2 data x model mesh)
against the same step unsharded on one chip.

Weights are random (``init_params`` with key 0); prompts and batches come
from ``--seed``.  Timings printed here are smoke timings of one cold run,
not benchmark results.  The last line of stdout is one JSON object naming
the device.  Any failed check raises, so the script exits nonzero.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import statistics
import sys
import tempfile
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.runtime.compile_cache import enable_persistent_cache  # noqa: E402

ARCH = "smollm-135m"
W = 8                    # serving batch width (executor rows)
N_REQUESTS = 16
PROMPT_LEN = 120         # one length: prefill compiles once
MAX_NEW = 32             # 120 + 32 crosses the 128-token page boundary
TRAIN_BATCH, TRAIN_SEQ = 8, 1024
TRAIN_STEPS = 10
SHARDED_STEPS = 4

# The op a Pallas TPU kernel lowers to in compiled HLO.
KERNEL_OP = "tpu_custom_call"

# Kernel vs reference attention.  Both sides accumulate in float32 and
# round their output to bf16 once.  The output is a convex combination
# of V rows, so |o| <= max|V| and one bf16 rounding moves it by at most
# half an ulp, 2**-8 * max|V|; two roundings give 2**-7 * max|V|.
# Either side's float32 probability x V product may run as one bf16
# pass, rounding each weight by 2**-9 of itself: at most 2**-9 * max|V|
# per side.  2**-6 * max|V| bounds the sum with room to spare.
ATTN_TOL_REL = 2.0 ** -6
# Kernel vs reference logits, float32 out of one full 30-layer bf16
# decode step.  A last-bit difference in any layer's attention flips
# bf16 roundings of the residual stream in every later layer, so the
# two sides differ at the model's bf16 noise floor, not at one ulp.  On
# a CPU, at this width and these lengths, that floor is 3.6% of the
# largest |logit| (bf16 model vs its float32 self; 3.9% with the
# attention probabilities rounded to bf16), while attending one
# position too few moves the logits by 37%.  Tolerated: 2**-3 (12.5%)
# of the largest |logit|.  Tokens are not compared: random-init logits
# have near-ties.
LOGITS_TOL_REL = 2.0 ** -3
# Sharded vs one-chip training loss.  The same bf16 math reduced in a
# different order (data-parallel gradient sums, tensor-parallel
# contractions); the float32 loss may drift by 2**-7 of its value.
LOSS_TOL_REL = 2.0 ** -7


class SmokeFailure(RuntimeError):
    """A check of this script failed."""


def check(ok: bool, msg: str) -> None:
    if not ok:
        raise SmokeFailure(msg)


def say(msg: str) -> None:
    print(msg, flush=True)


def check_device(need: int) -> dict:
    devs = jax.devices()
    d = devs[0]
    say(f"device: platform={d.platform} kind={d.device_kind} "
        f"count={len(devs)}")
    if d.platform != "tpu":
        raise SystemExit(f"chip_smoke: needs a TPU, found platform "
                         f"{d.platform!r}")
    if len(devs) < need:
        raise SystemExit(f"chip_smoke: needs {need} TPU devices, found "
                         f"{len(devs)}")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}


def phase_kernel_vs_ref(cfg, seed: int) -> None:
    from repro.kernels.paged_attention.ops import paged_attention_decode
    from repro.serve.kv_cache import BLOCK_TOKENS

    bt, nb, n_layers, layer = BLOCK_TOKENS, 4, 2, 1
    n_pages = W * nb + 1
    # an inactive row, lengths off and on page edges, and a full table
    lengths = jnp.asarray([0, 1, 100, bt, bt + 1, 300, 383, nb * bt],
                          jnp.int32)
    ks = jax.random.split(jax.random.key(seed), 5)
    q = jax.random.normal(ks[0], (W, cfg.num_heads, cfg.head_dim),
                          jnp.bfloat16)
    new = (W, cfg.num_kv_heads, cfg.head_dim)
    k = jax.random.normal(ks[1], new, jnp.bfloat16)
    v = jax.random.normal(ks[2], new, jnp.bfloat16)
    pages = (n_layers, cfg.num_kv_heads, n_pages, cfg.head_dim, bt)
    kp = jax.random.normal(ks[3], pages, jnp.bfloat16)
    vp = jax.random.normal(ks[4], pages, jnp.bfloat16)
    tables = jnp.asarray(np.random.default_rng(seed).permutation(n_pages)
                         [:W * nb].reshape(W, nb), jnp.int32)
    tol = ATTN_TOL_REL * float(jnp.max(jnp.abs(vp.astype(jnp.float32))))
    inactive = np.asarray(lengths) == 0
    for window in (0, 64):
        res = {impl: paged_attention_decode(
            q, k, v, kp, vp, layer, tables, lengths, window=window,
            impl=impl, interpret=False) for impl in ("kernel", "ref")}
        out = {impl: np.asarray(r[0].astype(jnp.float32))
               for impl, r in res.items()}
        diff = float(np.max(np.abs(out["kernel"] - out["ref"])))
        say(f"kernel vs ref attention (window={window}): max|diff|={diff} "
            f"tol={tol}")
        for impl, o in out.items():
            check(not np.any(o[inactive]),
                  f"{impl}: inactive rows are not exactly zero")
        check(diff <= tol, f"attention kernel vs ref {diff} > {tol}")
        check(all(bool(jnp.array_equal(a, b)) for a, b in
                  zip(res["kernel"][1:], res["ref"][1:])),
              "kernel and ref wrote the pools differently")


def phase_serve(cfg, seed: int) -> None:
    from repro.kernels.paged_attention.ops import resolve_impl
    from repro.launch.serve import run_continuous_server
    from repro.models import model
    from repro.serve import ServeRequest
    from repro.serve.batched_executor import JaxBatchedExecutor

    rng = np.random.default_rng(seed)
    t_submit = time.monotonic()
    reqs = [ServeRequest(rid=i, prompt_len=PROMPT_LEN, max_new=MAX_NEW,
                         t_submit=t_submit,
                         prompt=rng.integers(0, cfg.vocab_size, PROMPT_LEN)
                         .astype(np.int32))
            for i in range(N_REQUESTS)]
    t0 = time.monotonic()
    engine, rep = run_continuous_server(cfg, reqs, W, slo_ttft=0.0,
                                        slo_tpot=0.0)
    ex = engine.executor
    check(isinstance(ex, JaxBatchedExecutor),
          f"make_executor picked {type(ex).__name__}, not the batched "
          f"paged-decode executor")
    say(f"serve: {rep['requests']} requests, {rep['tokens']} tokens in "
        f"{time.monotonic() - t0:.1f}s wall (smoke timing, compiles "
        f"included)")
    for r in reqs:
        check(len(r.out_tokens) == MAX_NEW,
              f"request {r.rid} got {len(r.out_tokens)} tokens, "
              f"not {MAX_NEW}")
        check(all(0 <= t < cfg.vocab_size for t in r.out_tokens),
              f"request {r.rid} has a token outside [0, vocab)")
    check(ex.decode_compiles() == 1,
          f"decode compiled {ex.decode_compiles()} times, not once")
    check(resolve_impl("auto") == "kernel",
          f"attn_impl 'auto' resolves to {resolve_impl('auto')!r}")
    hlo = ex._decode.lower(
        ex.params, jnp.asarray(ex._tok), jnp.asarray(ex._len), ex._kp,
        ex._vp, jnp.asarray(ex._tables)).compile().as_text()
    check(KERNEL_OP in hlo, f"no {KERNEL_OP} in the compiled decode")
    say(f"serve: decode compiled once and holds {KERNEL_OP}")

    # one decode step over the pool the run left behind, each row on its
    # own pages, at lengths on and off page edges (row 1 inactive)
    tables = jnp.arange(W * ex.nb_max, dtype=jnp.int32).reshape(W, -1)
    full = ex.nb_max * ex.block_tokens
    lengths = jnp.asarray([PROMPT_LEN + MAX_NEW, 0, 1, 127, 128, 129,
                           full - 1, full], jnp.int32)
    tok = jnp.asarray(rng.integers(0, cfg.vocab_size, W), jnp.int32)
    logits = {}
    for impl in ("kernel", "ref"):
        step = jax.jit(model.paged_decode_fn(cfg, attn_impl=impl))
        out, _, _ = step(ex.params, tok, lengths, ex._kp, ex._vp, tables)
        logits[impl] = np.asarray(out.astype(jnp.float32))
    active = np.asarray(lengths) > 0
    ref = logits["ref"][active]
    check(bool(np.all(np.isfinite(ref))), "reference logits not finite")
    diff = float(np.max(np.abs(logits["kernel"][active] - ref)))
    tol = LOGITS_TOL_REL * float(np.max(np.abs(ref)))
    say(f"serve: decode logits kernel vs ref max|diff|={diff} tol={tol}")
    check(diff <= tol, f"decode logits kernel vs ref {diff} > {tol}")


def phase_train(cfg, seed: int) -> None:
    from repro.runtime.orchestrator import Orchestrator, RunConfig

    with tempfile.TemporaryDirectory(prefix="chip_smoke_ckpt_") as ckpt:
        # checkpoint_every past the last step: only the closing save runs
        run = RunConfig(steps=TRAIN_STEPS, batch=TRAIN_BATCH, seq=TRAIN_SEQ,
                        checkpoint_every=TRAIN_STEPS + 1, ckpt_dir=ckpt,
                        job_id=f"chip-smoke-{ARCH}", seed=seed)
        orc = Orchestrator(cfg, run)
        out = orc.run()
    losses = out["losses"]
    check(out["start_step"] == 0, f"resumed at step {out['start_step']}")
    check(len(losses) == TRAIN_STEPS,
          f"{len(losses)} of {TRAIN_STEPS} steps ran")
    check(bool(np.all(np.isfinite(losses))), f"non-finite loss: {losses}")
    check(out["ckpt_metrics"]["n_saves"] == 1,
          f"{out['ckpt_metrics']['n_saves']} checkpoints, not 1")
    say(f"train: losses {losses}")
    say(f"train (smoke timings, not benchmark results): compile "
        f"{out['compile_s']:.2f}s, median step "
        f"{statistics.median(orc.step_times):.4f}s at batch {TRAIN_BATCH} "
        f"x seq {TRAIN_SEQ}")


def phase_four_chips(cfg, seed: int) -> None:
    from repro.core.hlo_analysis import collective_stats
    from repro.launch.mesh import make_dev_mesh
    from repro.launch.strategy import (init_train_state, jit_train_step,
                                       make_train_step)
    from repro.models.config import ShapeConfig
    from repro.optim import AdamWConfig
    from repro.parallel.ctx import parallel_ctx

    batch = {"tokens": jax.random.randint(
        jax.random.key(seed), (TRAIN_BATCH, TRAIN_SEQ), 0, cfg.vocab_size,
        jnp.int32)}

    def run(step, state, batch):
        losses = []
        for _ in range(SHARDED_STEPS):
            state, m = step(state, batch)
            losses.append(float(m["loss"]))
        return state, losses

    state = init_train_state(cfg, jax.random.key(0))
    one = jax.jit(make_train_step(cfg, AdamWConfig()), donate_argnums=(0,))
    _, one_losses = run(one.lower(state, batch).compile(), state, batch)
    say(f"one chip: losses {one_losses}")

    mesh = make_dev_mesh(data=2, model=2)
    fn, _, ctx = jit_train_step(
        cfg, ShapeConfig("chip_smoke", "train", TRAIN_SEQ, TRAIN_BATCH),
        mesh)
    state = init_train_state(cfg, jax.random.key(0), mesh)
    with parallel_ctx(ctx):
        sharded = fn.lower(state, batch).compile()
    batch = jax.device_put(batch, sharded.input_shardings[0][1])
    state, sh_losses = run(sharded, state, batch)
    say(f"2x2 mesh: losses {sh_losses}")
    coll = collective_stats(sharded.as_text())
    say(f"2x2 mesh collectives: count {coll.count_by_kind} "
        f"bytes {coll.bytes_by_kind}")

    want = set(mesh.devices.flat)
    for path, leaf in jax.tree_util.tree_leaves_with_path(state):
        check(leaf.sharding.device_set == want,
              f"{jax.tree_util.keystr(path)} lives on "
              f"{len(leaf.sharding.device_set)} devices, not the mesh's 4")
    diff = max(abs(a - b) / abs(b) for a, b in zip(sh_losses, one_losses))
    say(f"2x2 mesh vs one chip: max relative loss diff {diff} "
        f"tol={LOSS_TOL_REL}")
    check(bool(np.all(np.isfinite(sh_losses))), "non-finite sharded loss")
    check(diff <= LOSS_TOL_REL,
          f"sharded vs one-chip loss {diff} > {LOSS_TOL_REL}")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 2x2-mesh train step vs one chip")
    args = ap.parse_args(argv)

    device = check_device(4 if args.four_chips else 1)
    say(f"compile cache: {enable_persistent_cache()}")
    cfg = get_config(ARCH)
    phases = ([phase_four_chips] if args.four_chips else
              [phase_kernel_vs_ref, phase_serve, phase_train])
    for phase in phases:
        t0 = time.monotonic()
        phase(cfg, args.seed)
        say(f"{phase.__name__}: passed in {time.monotonic() - t0:.1f}s")
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
