"""Where JAX keeps its persistent compilation cache, and what its key holds.

Every entry point calls :func:`init_compile_cache` once, before it compiles
anything. ``JAX_COMPILATION_CACHE_DIR``, when set, is read by JAX itself and
wins; otherwise the cache lives at ``<repo>/.jax_cache``. The path is part of
the cache's key, so it is fixed: never a temporary, pid- or time-named one.

The key includes each operation's metadata: its ``jax.named_scope`` path
(the ``cada.*`` phase names of the trainer step) and the file name and line
that made it. A profile is split by phase from the executable's own
metadata, and a key without it would serve an executable compiled from code
with other names. Only the innermost source frame is kept, by file name
alone, so neither the checkout's path nor the caller that compiles the
step changes the key. (Turning full tracebacks off instead would drop the
name stack from most ops' ``op_name``.)
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def init_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    jax.config.update("jax_traceback_in_locations_limit", 1)
    jax.config.update("jax_hlo_source_file_canonicalization_regex", r".*/")
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE))
    return str(REPO_CACHE)
