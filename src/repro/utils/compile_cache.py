"""JAX's persistent compilation cache, at a place a later run finds again.

Entry points call ``use_compile_cache()`` once, before their first compile.
When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads the variable itself and
this sets nothing. Otherwise the cache goes to ``.jax_cache`` at the root of
the checkout: a fixed path, since the directory is where the next run looks,
so it never holds a temporary name, a pid or the time.
"""
from __future__ import annotations

import os
from pathlib import Path

__all__ = ["CACHE_ENV", "DEFAULT_CACHE_DIR", "use_compile_cache"]

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory it lands in."""
    env = os.environ.get(CACHE_ENV)
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)
