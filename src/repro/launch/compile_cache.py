"""Where JAX's persistent compilation cache lives.

Call ``enable_compile_cache()`` before the first compile.  When
``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and nothing else
is set here; otherwise the cache goes to ``<checkout>/.jax_cache``.  The
path is fixed on purpose: it is part of what a later run must find again,
so it never depends on a temporary name, a process id or the time.
"""
from __future__ import annotations

import os
import pathlib

import jax

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_CACHE = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    path = os.environ.get(CACHE_ENV)
    if not path:
        path = str(CHECKOUT_CACHE)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
