"""Where the entry points keep JAX's persistent compilation cache.

If ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing is
set here. Otherwise the cache is ``.jax_cache/`` at the checkout root: a
fixed path, since the path is part of what a later run must find again.
"""
from __future__ import annotations

import os
import pathlib

CHECKOUT_ROOT = pathlib.Path(__file__).resolve().parents[3]
DEFAULT_DIR = CHECKOUT_ROOT / ".jax_cache"


def use_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory (before
    the first compile) and return that directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
