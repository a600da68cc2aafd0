"""Host spans of the serve path, on the profiler's clock.

``span(name)`` is a ``jax.profiler.TraceAnnotation`` when JAX is
already imported and a shared null context otherwise, so this package
stays importable without JAX and a simulated run never imports it just to
trace.  An annotation costs about a microsecond while no profiler runs;
under a profiler it lands on the ``/host:CPU`` plane, on the clock of the
device planes.

Every span the serve path opens is named in ``NAMES``:

  * ``serve.prefill``: one executor prefill call; inside it
    ``serve.prefill.request`` per request (row, prefill, argmax, page ids,
    scatter dispatch), then ``serve.prefill.sync`` (the wait and the token
    reads);
  * ``serve.decode``: one executor decode call, with the paged kernel's
    ``pages_walked`` and ``pages_gridded`` as its arguments (see
    ``JaxBatchedExecutor.decode``); inside it
    ``serve.decode.prepare`` (gather-map refresh and the host-to-device
    copies), ``serve.decode.dispatch`` (the jitted step returning) and
    ``serve.decode.sync`` (the wait and the read-back);
  * ``serve.engine.admit``, ``serve.engine.kv_grow`` (KV growth and any
    preemption) and ``serve.engine.detach`` (free, release, ledger
    flush): the engine's own work, outside the executor's spans.
"""
from __future__ import annotations

import contextlib
import sys

NAMES = (
    "serve.prefill", "serve.prefill.request", "serve.prefill.sync",
    "serve.decode", "serve.decode.prepare", "serve.decode.dispatch",
    "serve.decode.sync",
    "serve.engine.admit", "serve.engine.kv_grow", "serve.engine.detach",
)

_NULL = contextlib.nullcontext()


def span(name: str, **args):
    """A host span ``name`` (see module doc); ``args`` become the trace
    event's arguments."""
    jax = sys.modules.get("jax")
    if jax is None:
        return _NULL
    return jax.profiler.TraceAnnotation(name, **args)
