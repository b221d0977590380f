"""JAX persistent compilation cache for the launchers.

Called from ``serve.main`` and ``chip_smoke.py`` before the first compile,
never at import time.  The cache path is part of each entry's key, so it
is a fixed directory: ``JAX_COMPILATION_CACHE_DIR`` when set (JAX reads
it itself, so nothing is set here), else ``<checkout>/.jax_cache``.
"""
from __future__ import annotations

import os

import jax

CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))


def setup_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its fixed directory and
    return that directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = os.path.join(CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
