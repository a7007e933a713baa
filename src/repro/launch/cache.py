"""Persistent compilation cache shared by the entry points.

A cold process compiles every program it runs; on the chip that is a large
part of a short run. JAX keeps compiled programs on disk when it has a
cache directory: it reads ``JAX_COMPILATION_CACHE_DIR`` itself, and where
that is unset the entry points use ``<checkout>/.jax_cache``. The path is
part of every cache key, so it is fixed — never built from a temporary
name, a pid or the time.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"

#: the checkout's own cache directory (listed in .gitignore).
CHECKOUT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on and return the directory it uses:
    ``$JAX_COMPILATION_CACHE_DIR`` when set (JAX already honours it, so no
    other directory is configured), else :data:`CHECKOUT_DIR`."""
    env = os.environ.get(ENV)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_DIR))
    return str(CHECKOUT_DIR)
