"""JAX persistent compilation cache for the repo's entry points.

Call `enable()` from a script's `main()` (never at import time, never
from tests).  When `JAX_COMPILATION_CACHE_DIR` is set, JAX reads it
itself and nothing is overridden; otherwise the cache lives at the fixed
path `<repo root>/.jax_cache` (listed in `.gitignore`).  The path is part
of the cache key, so it never depends on a temporary name, a pid or the
time.
"""
from __future__ import annotations

import os
import pathlib

import jax

REPO_ROOT = pathlib.Path(__file__).resolve().parents[2]
DEFAULT_DIR = REPO_ROOT / ".jax_cache"


def enable() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
