"""JAX's persistent compilation cache for the entry points.

Each entry point's ``main`` calls ``enable_compile_cache`` first; nothing
calls it at import.  Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads
it itself and this module sets no other directory.  Otherwise the cache
is ``.jax_cache/`` at the root of the checkout: a fixed path, because the
path is part of what a later process must find again.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory it uses."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
