"""Persistent XLA compilation cache for the entry points.

Every entry point calls :func:`enable_compile_cache` before its first
compile, so a second run of the same program reads its compiled
executables back instead of compiling again.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

# A fixed directory inside the checkout (git-ignored).  The directory is
# part of what a later run must find again, so it never depends on the
# process, the time or the temp dir.
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    nothing is set here.  Otherwise the cache goes to ``<checkout>/.jax_cache``.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)
