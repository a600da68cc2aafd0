"""Host spans inside the serve path (``repro.serve.spans``): one engine run
over the batched executor, under the profiler, puts every span on the host
plane, nested as the module documents; the executor's programs carry their
own names; and without JAX imported a span is a shared no-op."""
import glob
import os
import pathlib
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke
from repro.serve import spans
from repro.serve.batched_executor import JaxBatchedExecutor
from repro.serve.engine import ContinuousServeEngine, ServeRequest

MAX_LEN = 32


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """(executor, {span name: [(start, end)]}, the ``serve.decode``
    events' arguments) of one traced run, which prefills each of its four
    requests once."""
    cfg = get_smoke("smollm-135m")
    ex = JaxBatchedExecutor(cfg, MAX_LEN, 3)
    rng = np.random.default_rng(3)
    reqs = [ServeRequest(rid=i, prompt_len=p, max_new=n, t_submit=0.0,
                         prompt=rng.integers(0, cfg.vocab_size, p)
                         .astype(np.int32))
            for i, (p, n) in enumerate([(6, 4), (9, 1), (5, 3), (7, 2)])]
    d = tmp_path_factory.mktemp("trace")
    with jax.profiler.trace(str(d)):
        ContinuousServeEngine(3, ex, kv_cache=ex.kv).run(reqs)
    path, = glob.glob(str(d / "**" / "*.xplane.pb"), recursive=True)
    host, = [p for p in jax.profiler.ProfileData.from_file(path).planes
             if p.name == "/host:CPU"]
    found, args = {}, []
    for line in host.lines:
        for ev in line.events:
            if ev.name in spans.NAMES:
                found.setdefault(ev.name, []).append(
                    (ev.start_ns, ev.start_ns + ev.duration_ns))
            if ev.name == "serve.decode":
                args.append(dict(ev.stats))
    return ex, found, args


def _within(inner, outers):
    return any(s <= inner[0] and inner[1] <= e for s, e in outers)


def test_every_span_lands_on_the_host_plane(served):
    _, found, _ = served
    assert set(found) == set(spans.NAMES)


def test_executor_spans_nest_in_their_call(served):
    _, found, _ = served
    for call in ("serve.prefill", "serve.decode"):
        for name in spans.NAMES:
            if name.startswith(call + "."):
                assert all(_within(ev, found[call]) for ev in found[name])
    for s, e in found["serve.decode"]:
        phases = [[ev for ev in found["serve.decode." + p]
                   if s <= ev[0] and ev[1] <= e][0]
                  for p in ("prepare", "dispatch", "sync")]
        assert phases[0][1] <= phases[1][0] and phases[1][1] <= phases[2][0]
    assert len(found["serve.prefill.request"]) == 4
    for s, e in found["serve.prefill"]:
        reqs = [ev for ev in found["serve.prefill.request"]
                if s <= ev[0] and ev[1] <= e]
        sync, = [ev for ev in found["serve.prefill.sync"]
                 if s <= ev[0] and ev[1] <= e]
        assert reqs and all(ev[1] <= sync[0] for ev in reqs)


def test_engine_spans_lie_outside_the_executor_calls(served):
    _, found, _ = served
    calls = found["serve.prefill"] + found["serve.decode"]
    for name in ("serve.engine.admit", "serve.engine.kv_grow",
                 "serve.engine.detach"):
        for s, e in found[name]:
            assert all(e <= cs or ce <= s for cs, ce in calls)


def test_decode_span_carries_the_page_counts(served):
    """Each ``serve.decode`` event carries the pages the kernel walks and
    the pages a (rows, pages) grid would step; summed, the executor's
    running totals."""
    ex, found, args = served
    assert len(args) == len(found["serve.decode"])
    assert sum(int(a["pages_walked"]) for a in args) == ex.pages_walked > 0
    assert all(int(a["pages_gridded"]) == ex.n_slots * ex.nb_max
               for a in args)
    assert sum(int(a["pages_gridded"]) for a in args) == ex.pages_gridded


def test_executor_programs_carry_their_names(served):
    ex, _, _ = served
    batch = {"tokens": jnp.zeros((1, 8), jnp.int32)}
    assert ex._prefill.lower(ex.params, batch).as_text().startswith(
        "module @jit_prefill")
    _, cache = jax.eval_shape(ex._prefill, ex.params, batch)
    idx = jax.ShapeDtypeStruct((8,), jnp.int32)
    assert ex._scatter.lower(cache, ex._kp, ex._vp, idx, idx).as_text() \
        .startswith("module @jit_scatter_prefill_pages")
    w = jax.ShapeDtypeStruct((ex.n_slots,), jnp.int32)
    tables = jax.ShapeDtypeStruct((ex.n_slots, ex.nb_max), jnp.int32)
    assert ex._decode.lower(ex.params, w, w, ex._kp, ex._vp, tables) \
        .as_text().startswith("module @jit__step")


def test_a_span_is_a_shared_noop_without_jax(monkeypatch):
    monkeypatch.delitem(sys.modules, "jax")
    a = spans.span("serve.decode")
    assert a is spans.span("serve.engine.admit")
    with a:
        pass


def test_a_simulated_run_does_not_import_jax():
    code = ("import sys\n"
            "from repro.serve import ContinuousServeEngine, "
            "SimulatedExecutor, synthetic_requests\n"
            "ContinuousServeEngine(2, SimulatedExecutor()).run("
            "synthetic_requests([0.0, 0.01, 0.02], max_new=(2, 4)))\n"
            "assert 'jax' not in sys.modules\n")
    src = pathlib.Path(__file__).resolve().parents[1] / "src"
    subprocess.run([sys.executable, "-c", code], check=True,
                   env=dict(os.environ, PYTHONPATH=str(src)), timeout=120)
