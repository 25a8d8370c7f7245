"""Where JAX keeps its persistent compilation cache.

Entry points call :func:`use_compile_cache` once at start — never at
import.  When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself
and this sets nothing.  Otherwise, run from a checkout (the ``src``
layout), the cache goes to ``.jax_cache`` at the checkout's root: a fixed
path, because the path is part of the cache key, so a directory named
after a pid, a temp name or the time never hits.  An installed copy of
the package has no checkout, so there it sets nothing and JAX keeps no
persistent cache.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT = Path(__file__).resolve().parents[3]


def use_compile_cache() -> str | None:
    """Point JAX's compilation cache at its directory; returns the path,
    or None outside a checkout with no ``JAX_COMPILATION_CACHE_DIR``."""
    if os.environ.get(ENV):
        return os.environ[ENV]
    if not (CHECKOUT / "pyproject.toml").is_file():
        return None
    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
