"""Where JAX keeps its persistent compilation cache.

A cache entry is keyed by the directory it lives in as much as by the
program, so the directory must not move between runs: no temporary,
pid- or time-named paths. ``JAX_COMPILATION_CACHE_DIR``, when set, is
read by JAX itself and wins; otherwise the cache goes to ``.jax_cache/``
at the root of this checkout (git-ignored).
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory.
    Call once at the start of an entry point, before anything compiles."""
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return jax.config.jax_compilation_cache_dir
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
