"""JAX's persistent compilation cache for the entry points that run on a chip.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it, and this
sets nothing.  Otherwise the cache lives at one fixed path inside the
checkout (``.jax_cache/``, git-ignored): the directory is part of the cache
key, so it is never built from a temp name, a pid or the time.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
