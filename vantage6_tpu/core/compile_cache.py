"""Where compiled device programs are kept between processes.

Every process that compiles for the device (``chip_smoke.py``, the bench
workers, ``v6t run``, a node daemon) calls :func:`enable_compile_cache`
once, before its first compile. The directory is placed from OUTSIDE: when
``JAX_COMPILATION_CACHE_DIR`` is set, jax already reads it and nothing is
set in code; otherwise the cache lives at ``<checkout>/.jax_cache``. The
path is part of jax's cache key, so it is fixed — no temp name, pid or
time in it — and a second process of the same checkout hits what the first
one compiled.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
# <checkout>/.jax_cache, resolved from this package's own path
DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on jax's persistent compilation cache; returns its directory."""
    placed = os.environ.get(ENV_VAR)
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
