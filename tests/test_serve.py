"""Serve launcher tests: ``run_continuous_server`` drives the continuous
engine over the real model, with the executor ``make_executor`` picks by
family — batched paged decode for a dense decoder, per-slot batch-1
decode for an encoder-decoder (encoder frames) and for a recurrent
model (recurrent state).  Each family serves one shared run: six
requests at batch 4, under an injected ``TickClock``."""
import json

import numpy as np
import pytest

from repro.configs import get_smoke
from repro.core.goodput import Layer, Phase
from repro.launch import serve
from repro.launch.serve import TickClock, run_continuous_server
from repro.serve import BLOCK_TOKENS, ServeRequest
from repro.serve.batched_executor import JaxBatchedExecutor
from repro.serve.jax_executor import JaxSlotExecutor

FAMILIES = {
    "smollm-135m": JaxBatchedExecutor,     # dense: paged decode
    "whisper-medium": JaxSlotExecutor,     # encdec: encoder frames
    "rwkv6-3b": JaxSlotExecutor,           # ssm: recurrent state
}
BATCH, N_REQ, PROMPT, MAX_NEW, DT = 4, 6, 8, 4, 0.25


def _requests(cfg, clock, n=N_REQ, max_new=MAX_NEW):
    rng = np.random.default_rng(0)
    t_submit = clock()
    return [ServeRequest(rid=i, prompt_len=PROMPT, max_new=max_new,
                         t_submit=t_submit,
                         prompt=rng.integers(0, cfg.vocab_size, PROMPT)
                         .astype(np.int32))
            for i in range(n)]


def _serve(arch):
    cfg = get_smoke(arch)
    clock = TickClock(dt=DT)
    reqs = _requests(cfg, clock)
    engine, out = run_continuous_server(cfg, reqs, BATCH, slo_ttft=0.0,
                                        slo_tpot=0.0, clock=clock)
    return reqs, engine, out


@pytest.fixture(scope="module", params=sorted(FAMILIES))
def served(request):
    return (request.param,) + _serve(request.param)


def test_each_request_gets_max_new_tokens_counted_once(served):
    """Six requests at batch 4: the second wave is two requests wide, and
    each request's tokens are delivered and reported exactly once."""
    arch, reqs, _, out = served
    vocab = get_smoke(arch).vocab_size
    assert [len(r.out_tokens) for r in reqs] == [MAX_NEW] * N_REQ
    assert all(0 <= t < vocab for r in reqs for t in r.out_tokens)
    assert out["requests"] == N_REQ
    assert out["tokens"] == N_REQ * MAX_NEW
    assert out["arch"] == arch


def test_run_books_every_accounting_phase(served):
    _, _, engine, out = served
    ledger = engine.ledger
    for phase in (Phase.QUEUED, Phase.INIT, Phase.STEP, Phase.IDLE):
        assert ledger.phase_chip_time(phase) > 0.0, phase
    assert {"init", "step", "idle"} <= set(out["rg_breakdown"])
    assert sum(out["rg_breakdown"].values()) == pytest.approx(1.0)
    # serve segment tagging feeds the fleet-wide phase_kind split (Fig. 15)
    assert "serve" in ledger.segment_report("phase_kind", {"serve": 1.0})
    # cross-layer provenance: serve events carry emitter=serve (trace
    # source) plus a canonical stack-layer tag for attribution
    assert "serve" in ledger.segment_report("emitter", {"serve": 1.0})
    layers = set(ledger.segment_report("layer", {}))
    assert layers <= {l.value for l in Layer}
    assert {"model", "scheduling"} <= layers


def test_tick_clock_runs_are_bit_identical(served):
    """With a virtual clock the report and the ledger's interval totals
    are identical across runs, every float bit for bit."""
    arch, reqs, engine, out = served
    reqs2, engine2, out2 = _serve(arch)
    assert out2 == out
    assert engine2.ledger.totals() == engine.ledger.totals()
    assert [r.out_tokens for r in reqs2] == [r.out_tokens for r in reqs]


def test_timestamps_lie_inside_the_run_and_capacity_is_its_span(served):
    """Each op reads the clock once at its start and once after its host
    sync, so under TickClock(dt) every op costs one dt: two waves of one
    prefill and three decodes span 8 dt, the first wave's first tokens
    land one dt in, and the slots' intervals tile batch x span."""
    _, reqs, engine, out = served
    t_start = reqs[0].t_submit
    t_end = t_start + out["span"]
    assert out["span"] == 8 * DT
    assert [r.t_first for r in reqs[:BATCH]] == [t_start + DT] * BATCH
    for r in reqs:
        assert t_start < r.t_first <= r.t_done <= t_end
    assert max(r.t_done for r in reqs) == t_end
    assert out["capacity_chip_time"] == pytest.approx(BATCH * out["span"])
    booked = sum(engine.ledger.phase_chip_time(p)
                 for p in (Phase.INIT, Phase.STEP, Phase.IDLE))
    assert booked == pytest.approx(out["capacity_chip_time"])
    assert 0.0 < out["goodput"]["SG"] <= 1.0


def test_make_executor_picks_by_family(served):
    arch, _, engine, out = served
    assert type(engine.executor) is FAMILIES[arch]
    assert out["kv_cache"]["block_tokens"] == BLOCK_TOKENS
    if FAMILIES[arch] is JaxBatchedExecutor:
        # the engine shares the executor's allocator: it is the gather map
        assert engine.kv is engine.executor.kv
        assert engine.executor.decode_compiles() == 1


def test_batch_zero_raises():
    cfg = get_smoke("smollm-135m")
    with pytest.raises(ValueError, match="batch"):
        run_continuous_server(cfg, _requests(cfg, TickClock(dt=DT)), 0,
                              slo_ttft=0.0, slo_tpot=0.0)


def test_zero_dt_clock_reports_zero_throughput():
    """A zero-dt clock collapses the span: the report's rates are 0.0,
    not a ZeroDivisionError, and every token is still counted."""
    cfg = get_smoke("smollm-135m")
    clock = TickClock(dt=0.0)
    reqs = _requests(cfg, clock, n=2, max_new=2)
    _, out = run_continuous_server(cfg, reqs, 2, slo_ttft=0.0,
                                   slo_tpot=0.0, clock=clock)
    assert out["span"] == 0.0
    assert out["capacity_chip_time"] == 0.0
    assert out["slo_goodput"] == 0.0
    assert out["goodput"]["SG"] == 0.0
    assert out["tokens"] == 4


def _main(argv, capsys, monkeypatch):
    # the CLI turns on the on-disk compile cache; keep the test hermetic
    monkeypatch.setattr(serve, "enable_persistent_cache", lambda: None)
    serve.main(argv)
    return json.loads(capsys.readouterr().out)


SMOKE_CLI = ["--arch", "smollm-135m", "--smoke", "--requests", "3",
             "--batch", "2", "--prompt-len", "8", "--max-new", "3"]


def test_main_prints_a_json_report(capsys, monkeypatch):
    out = _main(SMOKE_CLI + ["--tick-dt", "0.25"], capsys, monkeypatch)
    assert out["engine"] == "continuous"
    assert out["arch"] == "smollm-135m"
    assert (out["requests"], out["tokens"], out["n_slots"]) == (3, 9, 2)


def test_main_spreads_bursty_arrivals(capsys, monkeypatch):
    out = _main(SMOKE_CLI + ["--span", "4", "--arrival", "bursty"],
                capsys, monkeypatch)
    assert (out["requests"], out["tokens"]) == (3, 9)
    assert out["span"] > 0.0
