"""Where JAX's persistent compilation cache lives, for every entry point.

``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself and nothing here
sets another path. Unset: the cache is ``<checkout>/.jax_cache`` — a fixed
path, because the cache directory is part of what a later run must find
again (a per-run temporary name would never hit).
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT = Path(__file__).resolve().parents[3]
ENV = "JAX_COMPILATION_CACHE_DIR"


def enable_compile_cache() -> str:
    """Point JAX's persistent compile cache at its directory; return it."""
    env = os.environ.get(ENV)
    if env:
        return env
    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
