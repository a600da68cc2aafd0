"""A serving cell: the program's continuous engine over its batched paged
executor, driven open-loop on the wall clock.

Set-up builds the executor and engine as ``launch/serve.py`` builds them
(``max_len`` = the mix's longest prompt + longest output, the executor's
allocator shared with the engine), puts the benchmark's seeded weights in
place of the executor's own, and serves one request of every prompt length
of the mix so that every program the window uses is compiled or loaded
before it opens.  The window then runs ``ContinuousServeEngine.run`` over
the mix, through the wall-clock adapter.  Once it has closed, the peak
memory is read, the program's state is freed, and the plain reference
checks a sample of the requests it finished.
"""
from __future__ import annotations

import dataclasses
import gc
import json
import shutil
import sys
import tempfile
import time
from typing import Dict, List, Optional

import numpy as np

import clock
import reference
import traffic
import xplane
import weights

SPANS = ("executor.prefill", "executor.decode", "wait_for_arrival")
# a host span from just after the profiler starts to just before it stops:
# the traced window on the trace's own clock
WINDOW_SPAN = "traced_window"
# The traced run traces this part of the window: from 40% of it on, for
# at most TRACE_S seconds, between two executor ops.
TRACE_FROM = 0.4
TRACE_S = 3.0
SAMPLE_TOKENS = 1200      # served tokens the correctness sample reaches
SAMPLE_ROWS = 8           # at most this many requests in it
ATTN_BUDGET = 4e9         # bytes of reference attention per block of rows


@dataclasses.dataclass
class ServeRun:
    """What a serving run measured; the metric readers take it."""
    kind: str
    seconds: float
    elapsed: float                  # window open to run end (s)
    cfg: Dict
    requests: List                  # the engine's requests due in the window
    calls: List                     # clock.Call, one per executor op
    wake_late: List[float]
    trace: Optional[xplane.Summary] = None
    peak: object = None
    setup_s: float = 0.0


def model_config(cfile: Dict, role: str, smoke: bool):
    """(the program's ModelConfig, the plain fields) for ``role``."""
    import jax.numpy as jnp
    from repro.models.config import ModelConfig

    fields = dict(cfile["model"])
    if smoke:
        fields.update(cfile["smoke"])
    cfg = ModelConfig(
        **{k: v for k, v in fields.items() if k != "compute_dtype"},
        compute_dtype=jnp.dtype(fields["compute_dtype"]),
        param_dtype=jnp.dtype(cfile[role]["param_dtype"]))
    return cfg, fields


def compile_counter():
    """A running count of lowerings and backend compiles in the process."""
    import jax

    counts = {"lowerings": 0, "backend_compiles": 0}

    def on(name, *_a, **_k):
        if name.endswith("jaxpr_to_mlir_module_duration"):
            counts["lowerings"] += 1
        elif name.endswith("backend_compile_duration"):
            counts["backend_compiles"] += 1

    jax.monitoring.register_event_duration_secs_listener(on)
    return counts


def sample(done: List, seed: int) -> List:
    """The longest finished request and others drawn from the seed, until
    the sample holds SAMPLE_TOKENS served tokens or SAMPLE_ROWS requests."""
    if not done:
        return []
    rng = np.random.default_rng([int(seed), 1])
    longest = max(done, key=lambda r: (len(r.out_tokens), r.rid))
    rest = [r for r in done if r is not longest]
    out, n = [longest], len(longest.out_tokens)
    for i in rng.permutation(len(rest)):
        if n >= SAMPLE_TOKENS or len(out) >= SAMPLE_ROWS:
            break
        out.append(rest[int(i)])
        n += len(rest[int(i)].out_tokens)
    return out


def reference_rows(fields: Dict, pad_to: int) -> int:
    per_row = fields["num_heads"] * pad_to * pad_to * 4 * 3
    return int(max(1, min(SAMPLE_ROWS, ATTN_BUDGET // per_row)))


def check(fields: Dict, seed: int, dtype, picked: List, pad_to: int,
          control: bool = False):
    """Widest reference-logit gap of the served tokens of ``picked``
    (and of the fp8 control's first choices, with ``control``)."""
    seqs = [(np.asarray(r.prompt), np.asarray(r.out_tokens, np.int32))
            for r in picked]
    g, gc_ = reference.served_gaps(fields, seed, dtype, seqs, pad_to,
                                   reference_rows(fields, pad_to), control)
    return float(np.max(g)), (float(np.max(gc_)) if control else None)


class Server:
    """The program's executor and the benchmark's weights, built once; a
    process may serve several windows with it (``calibrate.py`` does)."""

    def __init__(self, ctx):
        from repro.models.init import abstract_params
        from repro.serve.batched_executor import JaxBatchedExecutor

        self.counts = compile_counter()
        self.cfg, self.fields = model_config(ctx.cfile, "serve", ctx.smoke)
        self.pdt = self.cfg.param_dtype
        self.slots = ctx.cfile["serve"]["slots"]
        self.max_len = traffic.max_len(ctx.traffic)
        self.pad_to = -(-self.max_len // 128) * 128
        make = ctx.make_executor or JaxBatchedExecutor
        self.ex = make(self.cfg, self.max_len, self.slots)
        weights.check_layout(self.fields, self.pdt,
                             abstract_params(self.cfg))
        self.ex.params = None            # the benchmark's weights instead

    def load(self, seed: int) -> None:
        self.ex.params = None
        self.ex.params = weights.make(self.fields, seed, self.pdt)

    def warm(self, mix: Dict, seed: int) -> None:
        """Serve one request of every prompt length of the mix, so that
        every program the window runs is compiled or loaded."""
        import jax
        from repro.serve import ContinuousServeEngine, ServeRequest

        rng = np.random.default_rng([int(seed), 2])
        warm = [ServeRequest(rid=10**9 + i, prompt_len=p, max_new=3,
                             prompt=rng.integers(0, self.fields["vocab_size"],
                                                 p, dtype=np.int32))
                for i, p in enumerate(sorted(set(mix["prompt_menu"])))]
        ContinuousServeEngine(self.slots, self.ex, kv_cache=self.ex.kv) \
            .run(warm)
        jax.block_until_ready(self.ex.params)

    def window(self, mix: Dict, seed: int, seconds: float, trace: bool,
               t_start: float, peak=None):
        """Serve the mix's requests for one window on the wall clock.
        Returns (ServeRun, window facts, the engine's requests)."""
        import jax
        from repro.core.ledger import GoodputLedger
        from repro.serve import ContinuousServeEngine, ServeRequest

        ex = self.ex
        reqs = traffic.serve_requests(mix, self.fields["vocab_size"], seed,
                                      seconds)
        window = {r.rid for r in reqs if r.in_window}
        sreqs = [ServeRequest(rid=r.rid, prompt_len=len(r.prompt),
                              max_new=r.max_new, t_submit=r.t_submit,
                              prompt=r.prompt) for r in reqs]
        engine = ContinuousServeEngine(self.slots, None, kv_cache=ex.kv,
                                       ledger=GoodputLedger(window=60.0),
                                       arch=self.cfg.name)
        done = {"n": 0}

        def on_release(r):
            if r.rid in window and r.t_done > 0:
                done["n"] += 1

        inwin = [r for r in sreqs if r.rid in window]
        if mix["follow"] == "window":
            def should_stop(el):
                return el >= seconds
        else:
            # "first_token": past the window, until every request due in
            # it has its first token (for at most first_token_cap_s)
            cap = seconds + mix["first_token_cap_s"]

            def should_stop(el):
                return el >= seconds and (
                    el >= cap or all(r.t_first > 0 for r in inwin))

        tr = {"dir": None, "on": False, "t0": None, "span": None}
        most = {"rows": 0, "blocks": 0}

        def start_trace(el):
            tr["dir"] = tempfile.mkdtemp(prefix="chip_trace_")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0     # host spans only, not Python
            jax.profiler.start_trace(tr["dir"], profiler_options=opts)
            tr["span"] = jax.profiler.TraceAnnotation(WINDOW_SPAN)
            tr["span"].__enter__()
            tr["on"], ad.tracing, tr["t0"] = True, True, el

        def stop_trace():
            tr["span"].__exit__(None, None, None)
            jax.profiler.stop_trace()
            tr["on"], ad.tracing = False, False

        def on_op(el):
            most["rows"] = max(most["rows"], len(ex.rows))
            most["blocks"] = max(most["blocks"], ex.kv.used_blocks)
            if not trace:
                return
            if tr["dir"] is None and el >= TRACE_FROM * seconds:
                start_trace(el)
            elif tr["on"] and el >= tr["t0"] + min(TRACE_S, 0.3 * seconds):
                stop_trace()

        ad = clock.WallClockExecutor(
            ex, engine_time=lambda: engine.t,
            seq_len=lambda r: ex.kv.seq_len(r.rid),
            should_stop=should_stop, on_op=on_op, on_release=on_release,
            span=jax.profiler.TraceAnnotation if trace else None)
        engine.executor = ad
        jit_before = _jit_sizes(ex)
        counts_before = dict(self.counts)
        setup_s = time.monotonic() - t_start
        ad.open()
        try:
            engine.run(sreqs)
        except clock.StopRun:
            pass
        elapsed = ad.elapsed()
        if tr["on"]:
            stop_trace()
        late = ad.wake_late
        facts = {
            "window_jit_compiles": sum(_jit_sizes(ex)) - sum(jit_before),
            "window_lowerings":
                self.counts["lowerings"] - counts_before["lowerings"],
            "window_backend_compiles": self.counts["backend_compiles"]
                - counts_before["backend_compiles"],
            "generator_late_p50_ms":
                1e3 * float(np.median(late)) if late else 0.0,
            "generator_late_max_ms": 1e3 * float(max(late, default=0.0)),
            "elapsed_s": elapsed,
            "requests_in_window": len(window),
            "unfinished_in_window": len(window) - done["n"],
            "no_first_token": sum(1 for r in inwin if r.t_first == 0),
            "prefill_calls": sum(c.kind == "prefill" for c in ad.calls),
            "decode_calls": sum(c.kind == "decode" for c in ad.calls),
            # live rows and KV pages in use, at their most (read before
            # each op), against the slots and pages the pool holds
            "peak_live_rows": most["rows"],
            "peak_kv_pages": most["blocks"],
            "slots": self.slots,
            "pool_pages": ex.kv.n_blocks,
        }
        summary = None
        if tr["dir"] is not None:
            ops, progs, spans = xplane.read_xplane(
                xplane.find_xplane(tr["dir"]), SPANS + (WINDOW_SPAN,))
            summary = xplane.summarize_window(ops, progs, spans, WINDOW_SPAN)
            shutil.rmtree(tr["dir"], ignore_errors=True)
        result = ServeRun(kind="serve", seconds=seconds, elapsed=elapsed,
                          cfg=self.fields,
                          requests=inwin,
                          calls=ad.calls, wake_late=late, trace=summary,
                          peak=peak, setup_s=setup_s)
        return result, facts, sreqs

    def reset(self, sreqs) -> None:
        """Release what a window cut short left in the executor and in the
        KV allocator (a request may hold pages before its prefill)."""
        for r in sreqs:
            self.ex.release(r)
            try:
                self.ex.kv.free(r.rid)
            except KeyError:
                pass

    def free(self) -> None:
        self.ex.params = self.ex._kp = self.ex._vp = None
        self.ex = None
        gc.collect()


def run(ctx) -> Dict:
    """One serving run; returns the harness's result pieces."""
    marks = [("start", ctx.t_start)]
    srv = Server(ctx)
    marks.append(("executor", time.monotonic()))
    srv.load(ctx.seed)
    marks.append(("weights", time.monotonic()))
    srv.warm(ctx.traffic, ctx.seed)
    marks.append(("warm", time.monotonic()))
    print("setup_phases " + json.dumps(
        {b[0]: round(b[1] - a[1], 3) for a, b in zip(marks, marks[1:])}),
        file=sys.stderr, flush=True)
    result, facts, sreqs = srv.window(ctx.traffic, ctx.seed, ctx.seconds,
                                      ctx.trace, ctx.t_start, ctx.peak)
    memory = ctx.memory_peak()
    picked = sample([r for r in sreqs if r.t_done > 0], ctx.seed)
    fields, pdt, pad_to = srv.fields, srv.pdt, srv.pad_to
    srv.free()                       # before the reference runs
    t_ref = time.monotonic()
    gap, ctl = check(fields, ctx.seed, pdt, picked, pad_to,
                     control=ctx.control)
    print("reference " + json.dumps({
        "requests": len(picked),
        "tokens": sum(len(r.out_tokens) for r in picked),
        "seconds": round(time.monotonic() - t_ref, 3)}),
        file=sys.stderr, flush=True)
    if ctx.control:
        gap = ctl
    failed = (0 if ctx.traffic["follow"] == "window"
              else facts["no_first_token"])
    return {"run": result, "checks": {"served_logit_gap": gap},
            "attempted": facts["requests_in_window"], "failed": failed,
            "memory": memory, "window": facts}


def _jit_sizes(ex):
    return [f._cache_size() for f in (ex._prefill, ex._scatter, ex._decode)]
