"""Persistent XLA compilation cache for the entry points.

Each entry point's ``main`` calls ``enable_compile_cache()`` first, so a
second run of the same program loads its compiled executables instead of
compiling again. Importing the library never touches the cache.
"""
from __future__ import annotations

import os
from pathlib import Path

# <repo>/src/repro/utils/compile_cache.py -> <repo>/.jax_cache. A fixed path:
# the directory is part of the cache key, so one that moves never hits.
CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compile cache and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is honoured as JAX reads it;
    otherwise the cache lives at ``<repo>/.jax_cache``."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
