"""Where JAX keeps compiled programs between processes.

Call :func:`enable_compile_cache` from a program's entry point, never at
import. When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself
and nothing else is configured here. Otherwise the cache goes to
``<checkout>/.jax_cache``: a fixed path, because the path is part of
what a cached entry is found by, so a directory that moves never hits.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["enable_compile_cache"]

CHECKOUT = Path(__file__).resolve().parents[2]


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its path."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if path:
        return path
    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
