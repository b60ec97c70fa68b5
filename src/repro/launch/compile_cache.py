"""Persistent XLA compile cache, switched on by the entry points.

Each process otherwise compiles every program from cold. ``enable`` is
called at the top of an entry point's ``main`` — never at import, and never
from tests — so that a second run of the same program reads its compiled
code back instead of compiling it again.
"""
from __future__ import annotations

import os
from pathlib import Path

# fixed, at the checkout root: a cache directory that moves is never hit
CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable() -> str:
    """Turn the cache on and return its directory. Where
    ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and no other
    directory is set; otherwise the cache lives in ``CHECKOUT_CACHE``."""
    import jax

    # every program is kept, the per-stage chain jits included: they are
    # many and small, and each would otherwise recompile on every run
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
