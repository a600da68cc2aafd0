"""Batched paged-decode executor: token identity vs the per-slot
executor (dense + MoE), zero recompilation across admission/detach, and
the paged model path's logits equivalence (ref impl vs Pallas kernel in
interpret mode)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke
from repro.models import model
from repro.serve.batched_executor import JaxBatchedExecutor, make_executor
from repro.serve.engine import NO_SLO, ContinuousServeEngine, ServeRequest
from repro.serve.jax_executor import JaxSlotExecutor

MAX_LEN = 32


def _requests(cfg, n=10, seed=7):
    """Mixed prompt lengths and output budgets, all within MAX_LEN."""
    rng = np.random.default_rng(seed)
    reqs = []
    for i in range(n):
        plen = int(rng.integers(4, 13))
        reqs.append(ServeRequest(
            rid=i, prompt_len=plen, max_new=1 + i % 5, t_submit=0.0,
            prompt=rng.integers(0, cfg.vocab_size, plen).astype(np.int32)))
    return reqs


def _serve(cfg, batched: bool, n_slots=4, **ex_kw):
    reqs = _requests(cfg)
    if batched:
        ex = JaxBatchedExecutor(cfg, MAX_LEN, n_slots, **ex_kw)
        eng = ContinuousServeEngine(n_slots, ex, slo=NO_SLO, kv_cache=ex.kv)
    else:
        ex = JaxSlotExecutor(cfg, MAX_LEN)
        eng = ContinuousServeEngine(n_slots, ex, slo=NO_SLO)
    eng.run(reqs)
    return {r.rid: list(r.out_tokens) for r in reqs}, ex


@pytest.mark.parametrize("arch", ["smollm-135m", "deepseek-moe-16b"])
def test_batched_token_identical_to_per_slot(arch):
    """The acceptance property: one jitted decode at fixed width serves
    mixed-length live slots token-identically to per-slot batch-1 decode
    — on the dense AND the MoE config."""
    cfg = get_smoke(arch)
    per_slot, _ = _serve(cfg, batched=False)
    batched, ex = _serve(cfg, batched=True, attn_impl="ref")
    assert batched == per_slot
    assert sum(len(v) for v in batched.values()) > 0


def test_admission_detach_zero_recompilation():
    """10 requests with 8 distinct lengths churn through 4 rows — the
    batched decode must compile exactly once (the compile-count probe)."""
    cfg = get_smoke("smollm-135m")
    _, ex = _serve(cfg, batched=True, attn_impl="ref")
    assert ex.decode_compiles() == 1


def test_paged_step_kernel_matches_ref_logits():
    """The Pallas kernel (interpret mode — the real code path CI runs)
    and the XLA gather ref produce the same logits inside the full
    jitted model step."""
    cfg = get_smoke("smollm-135m")
    ex = JaxBatchedExecutor(cfg, MAX_LEN, 3, attn_impl="ref")
    # occupy rows with mixed lengths via a real engine run prefix
    reqs = _requests(cfg, n=3)
    for r in reqs:
        ex.kv.allocate(r.rid, r.prompt_len)
    ex.prefill(reqs)
    for r in reqs:
        ex.kv.append_token(r.rid)
        row = ex.rows[r.rid]
        ex._len[row] = ex.kv.seq_len(r.rid)
        table = ex.kv.block_table(r.rid)
        ex._tables[row, :len(table)] = table
    tok = jnp.asarray(ex._tok)
    lens = jnp.asarray(ex._len)
    bt = jnp.asarray(ex._tables)
    out, pools = {}, {}
    for impl in ("ref", "kernel"):
        step = model.paged_decode_fn(cfg, attn_impl=impl, interpret=True)
        logits, kp, vp = step(ex.params, tok, lens, ex._kp, ex._vp, bt)
        out[impl], pools[impl] = np.asarray(logits), (kp, vp)
    np.testing.assert_allclose(out["kernel"], out["ref"], atol=1e-4)
    assert np.array_equal(out["kernel"].argmax(-1), out["ref"].argmax(-1))
    # both write the step's K/V into the same places of the pool
    for k, r in zip(pools["kernel"], pools["ref"]):
        np.testing.assert_allclose(np.asarray(k, np.float32),
                                   np.asarray(r, np.float32), atol=1e-2)


def test_page_counters_count_each_live_rows_pages():
    """``pages_walked`` sums ceil(len / block_tokens) over the live rows
    of every decode call, read off the allocator as the call starts;
    ``pages_gridded`` is W x nb_max a call.  Prompts cross page ends
    while they decode, and a fifth request waits for a row."""
    cfg = get_smoke("smollm-135m")
    max_len, n_slots = 300, 4
    ex = JaxBatchedExecutor(cfg, max_len, n_slots, attn_impl="ref")
    rng = np.random.default_rng(5)
    reqs = [ServeRequest(rid=i, prompt_len=p, max_new=n, t_submit=0.0,
                         prompt=rng.integers(0, cfg.vocab_size, p)
                         .astype(np.int32))
            for i, (p, n) in enumerate([(125, 8), (3, 6), (250, 9),
                                        (129, 2), (60, 4)])]
    seen = {"calls": 0, "walked": 0, "most": 0}
    decode = ex.decode

    def counted(rs):
        pages = [-(-ex.kv.seq_len(r.rid) // ex.block_tokens) for r in rs]
        seen["calls"] += 1
        seen["walked"] += sum(pages)
        seen["most"] = max(seen["most"], *pages)
        return decode(rs)

    ex.decode = counted
    ContinuousServeEngine(n_slots, ex, slo=NO_SLO, kv_cache=ex.kv).run(reqs)
    assert seen["most"] == 3 and ex.nb_max == 3
    assert ex.pages_walked == seen["walked"]
    assert ex.pages_gridded == seen["calls"] * n_slots * ex.nb_max
    assert 0 < ex.pages_walked < ex.pages_gridded


def test_make_executor_falls_back_for_unpaged_families():
    cfg = get_smoke("rwkv6-3b")
    assert not model.supports_paged_decode(cfg, MAX_LEN)
    ex, kv = make_executor(cfg, MAX_LEN, 2)
    assert isinstance(ex, JaxSlotExecutor) and kv is None

    dense = get_smoke("smollm-135m")
    ex2, kv2 = make_executor(dense, MAX_LEN, 2)
    assert isinstance(ex2, JaxBatchedExecutor) and kv2 is ex2.kv


def test_windowed_config_rejected():
    """A sliding window narrower than max_len trims the prefill cache, so
    the paged path must refuse rather than serve wrong prefixes."""
    cfg = get_smoke("smollm-135m")
    windowed = dataclasses.replace(cfg, attention_window=8)
    assert not model.supports_paged_decode(windowed, MAX_LEN)
    with pytest.raises(ValueError, match="paged"):
        JaxBatchedExecutor(windowed, MAX_LEN, 2)
    # window >= max_len masks nothing — paged decode stays exact
    wide = dataclasses.replace(cfg, attention_window=MAX_LEN)
    assert model.supports_paged_decode(wide, MAX_LEN)


def test_rows_recycle_and_release():
    cfg = get_smoke("smollm-135m")
    ex = JaxBatchedExecutor(cfg, MAX_LEN, 2)
    reqs = _requests(cfg, n=2)
    for r in reqs:
        ex.kv.allocate(r.rid, r.prompt_len)
    ex.prefill(reqs)
    assert len(ex.rows) == 2 and not ex._free_rows
    ex.kv.free(reqs[0].rid)
    ex.release(reqs[0])
    assert len(ex.rows) == 1 and len(ex._free_rows) == 1
    row = 1 - ex.rows[reqs[1].rid]
    assert ex._len[row] == 0
    assert np.all(ex._tables[row] == ex.null_page)
