"""Ahead-of-time compilation cache (paper §5.2: "compile on cheap hardware,
store, and skip JIT on the accelerators").

Two layers:
  * jax's persistent compilation cache (XLA executable serialization) —
    turned on by the entry points through ``enable_persistent_cache``;
  * an in-process AOT registry keyed by (arch, shape, mesh, donation
    signature) holding `Lowered`/`Compiled` objects so repeated launches
    within one controller reuse executables.

`CompileClock` records compile wall-time per key; the Runtime-Goodput
benchmark (fig14) uses it to quantify the INIT-time saving of a warm cache.
"""
from __future__ import annotations

import os
import pathlib
import time
from typing import Any, Callable, Dict, Hashable, Tuple

import jax

# The directory is part of every cache key, so it must not move between
# runs: a fixed path at the repo root, never a temporary name.
DEFAULT_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_persistent_cache() -> str:
    """Turn on XLA's on-disk executable cache; returns its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is the cache (jax reads it
    itself and no other directory is set here); otherwise the cache is
    ``DEFAULT_CACHE_DIR``.  Call before the first compile.  This is the
    only place the repository sets ``jax_compilation_cache_dir``.
    """
    directory = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not directory:
        directory = str(DEFAULT_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", directory)
    pathlib.Path(directory).mkdir(parents=True, exist_ok=True)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return directory


class CompileClock:
    def __init__(self):
        self.events: Dict[Hashable, Dict[str, float]] = {}

    def record(self, key: Hashable, seconds: float, hit: bool):
        self.events[key] = {"seconds": seconds, "hit": float(hit)}

    @property
    def total_compile_s(self) -> float:
        return sum(e["seconds"] for e in self.events.values())


class AotCache:
    """In-process executable registry with compile-time accounting."""

    def __init__(self):
        self._store: Dict[Hashable, Any] = {}
        self.clock = CompileClock()

    def get_or_compile(self, key: Hashable,
                       build: Callable[[], Tuple[Any, tuple]]) -> Any:
        """build() -> (jitted_fn, abstract_args); returns Compiled."""
        if key in self._store:
            self.clock.record(key, 0.0, hit=True)
            return self._store[key]
        t0 = time.monotonic()
        fn, args = build()
        compiled = fn.lower(*args).compile()
        self.clock.record(key, time.monotonic() - t0, hit=False)
        self._store[key] = compiled
        return compiled

    def __contains__(self, key: Hashable) -> bool:
        return key in self._store
