"""The wall-clock adapter: it runs the serve engine's virtual clock on the
wall clock, without a change to the engine.

``ContinuousServeEngine`` advances its clock by the cost each executor op
returns, and jumps it to the next arrival when it has nothing to do.
Wrapped in this adapter, each op first sleeps until the wall clock reaches
the engine's current time (so an idle jump becomes a real wait), then runs
the real op and returns as its cost the wall time from that due time to
the op's end.  So at the end of every op the engine's clock equals the
wall clock (both counted from the window's start), and the engine's own
host work between ops, and a late admission, are charged to the next op,
as a user would see them.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable, List, Optional, Sequence


class StopRun(Exception):
    """Raised from an op to end the engine's run (the window has closed,
    or every request that is measured is done)."""


@dataclasses.dataclass
class Call:
    kind: str            # "prefill" | "decode"
    due: float           # engine time at entry (s after the window opens)
    start: float         # wall time the op started (same origin)
    end: float           # wall time the op returned (same origin)
    rows: int            # requests in the op
    tokens: int          # prefill: prompt tokens; decode: sum of lengths
    cost: float          # the executor's own host-clock cost of the call
    traced: bool = False


class WallClockExecutor:
    """Executor protocol (``prefill``/``decode``/``release``) over an inner
    executor, on the wall clock.

    ``engine_time`` reads the engine's virtual clock; ``should_stop`` is
    asked, with the time since the window opened, before every op;
    ``on_op`` is called with that time before every op (the traced run
    starts and stops the profiler there), ``on_release`` with every
    request the engine releases.  ``seq_len`` gives a request's
    sequence length at decode (the KV allocator's count).
    """

    def __init__(self, inner, engine_time: Callable[[], float],
                 seq_len: Callable[[object], int],
                 should_stop: Callable[[float], bool] = lambda t: False,
                 on_op: Callable[[float], None] = lambda t: None,
                 now: Callable[[], float] = time.monotonic,
                 sleep: Callable[[float], None] = time.sleep,
                 span: Optional[Callable[[str], object]] = None,
                 on_release: Callable[[object], None] = lambda r: None):
        self.inner = inner
        self.engine_time = engine_time
        self.seq_len = seq_len
        self.should_stop = should_stop
        self.on_op = on_op
        self.now = now
        self.sleep = sleep
        self.span = span
        self.on_release = on_release
        self.t0: Optional[float] = None
        self.calls: List[Call] = []
        self.wake_late: List[float] = []     # how late each wait woke
        self.tracing = False

    def open(self) -> None:
        """The window opens now: engine time 0 is this wall instant."""
        self.t0 = self.now()

    def elapsed(self) -> float:
        return self.now() - self.t0

    def _enter(self):
        due = self.t0 + self.engine_time()
        now = self.now()
        if now < due:
            if self.span is not None:
                with self.span("wait_for_arrival"):
                    self.sleep(due - now)
            else:
                self.sleep(due - now)
            now = self.now()
            self.wake_late.append(max(0.0, now - due))
        if self.should_stop(now - self.t0):
            raise StopRun
        self.on_op(now - self.t0)
        return due, self.now()

    def _run(self, kind: str, reqs: Sequence, tokens: int):
        due, start = self._enter()
        op = getattr(self.inner, kind)
        if self.span is not None:
            with self.span(f"executor.{kind}"):
                toks, cost = op(reqs)
        else:
            toks, cost = op(reqs)
        end = self.now()
        self.calls.append(Call(kind, due - self.t0, start - self.t0,
                               end - self.t0, len(reqs), tokens, cost,
                               self.tracing))
        return toks, end - due

    def prefill(self, reqs: Sequence):
        return self._run("prefill", reqs, sum(r.prompt_len for r in reqs))

    def decode(self, reqs: Sequence):
        return self._run("decode", reqs, sum(self.seq_len(r) for r in reqs))

    def release(self, req) -> None:
        self.inner.release(req)
        self.on_release(req)
