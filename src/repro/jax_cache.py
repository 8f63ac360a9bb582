"""JAX's persistent compilation cache, set once by every entry point.

Call `enable_compile_cache` before the first compile.  Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it and this sets no other
directory; otherwise the cache lives at ``<checkout>/.jax_cache`` — a fixed
path, because the path is part of what makes a later process find the
entries.  The scheduler's programs are small and most compile in well under
JAX's default one-second floor for writing an entry, so the floor is
dropped: every program is cached.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: the cache directory used when ``JAX_COMPILATION_CACHE_DIR`` is unset
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(DEFAULT_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
