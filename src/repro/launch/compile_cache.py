"""Persistent XLA compilation cache for the entry points.

Call :func:`enable_compile_cache` at the start of an entry point's
``main()``, never at import.  The cache key includes the directory, so the
default is one fixed directory inside the checkout (listed in
``.gitignore``): a directory that moves between runs never hits.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["CACHE_DIR", "enable_compile_cache"]

#: default cache directory: ``.jax_cache/`` at the root of the checkout
CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, where set, is left to JAX, which reads
    it itself; otherwise the cache goes to :data:`CACHE_DIR`.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
