"""JAX persistent compilation cache for the repo's entry points, and the
process's compile clock.

Call `enable()` from a script's `main()` (never at import time, never
from tests).  When `JAX_COMPILATION_CACHE_DIR` is set, JAX reads it
itself and nothing is overridden; otherwise the cache lives at the fixed
path `<repo root>/.jax_cache` (listed in `.gitignore`).  The path is part
of the cache key, so it never depends on a temporary name, a pid or the
time.  `enable()` also starts the compile clock (`clock()`), so every
compile after it is counted.
"""
from __future__ import annotations

import os
import pathlib
from typing import Dict, Optional

import jax

REPO_ROOT = pathlib.Path(__file__).resolve().parents[2]
DEFAULT_DIR = REPO_ROOT / ".jax_cache"


class CompileClock:
    """Sums JAX's own compile-duration events and counts persistent-cache
    hits and misses, from the moment it is made:

      trace_lower_s  tracing to a jaxpr and lowering it to an MLIR module
      compile_s      the backend compile; on a cache hit, the retrieval
      compiles       backend compiles (cache hits included)
    """
    TRACE_LOWER = ("/jax/core/compile/jaxpr_trace_duration",
                   "/jax/core/compile/jaxpr_to_mlir_module_duration")
    COMPILE = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.trace_lower_s = self.compile_s = 0.0
        self.compiles = self.hits = self.misses = 0

        def on_duration(event, secs, **_):
            if event in self.TRACE_LOWER:
                self.trace_lower_s += secs
            elif event == self.COMPILE:
                self.compile_s += secs
                self.compiles += 1

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self.hits += 1
            elif event == "/jax/compilation_cache/cache_misses":
                self.misses += 1
        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    @property
    def seconds(self) -> float:
        return self.trace_lower_s + self.compile_s

    def snapshot(self):
        return self.seconds, self.hits, self.misses

    def totals(self) -> Dict[str, float]:
        return {"compile_s": self.compile_s,
                "trace_lower_s": self.trace_lower_s,
                "compiles": self.compiles, "cache_hits": self.hits,
                "cache_misses": self.misses}


_CLOCK: Optional[CompileClock] = None


def clock() -> CompileClock:
    """The process's one compile clock; its listeners are registered on
    the first call."""
    global _CLOCK
    if _CLOCK is None:
        _CLOCK = CompileClock()
    return _CLOCK


def enable() -> str:
    """Turn the persistent compilation cache on and start the compile
    clock; returns the cache directory."""
    clock()
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
