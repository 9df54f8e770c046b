"""Where JAX keeps its persistent compilation cache for this checkout.

A cache only hits when its directory stays put (the path is part of the
key), so it lives at one fixed place: ``JAX_COMPILATION_CACHE_DIR`` when
that is set (JAX reads the variable itself), else ``.jax_cache`` at the
root of the checkout. Drivers call :func:`enable_compilation_cache`
before their first compile.
"""
from __future__ import annotations

import os
import pathlib

import jax

CHECKOUT_CACHE = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compilation_cache() -> str:
    """Turn the persistent cache on and return its directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(CHECKOUT_CACHE)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
