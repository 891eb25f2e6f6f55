"""Where JAX keeps its persistent compilation cache.

Every entry point calls :func:`init_compile_cache` once, before it compiles
anything. ``JAX_COMPILATION_CACHE_DIR``, when set, is read by JAX itself and
wins; otherwise the cache lives at ``<repo>/.jax_cache``. The path is part of
the cache's key, so it is fixed: never a temporary, pid- or time-named one.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def init_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE))
    return str(REPO_CACHE)
