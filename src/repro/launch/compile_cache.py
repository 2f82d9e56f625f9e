"""Where JAX's persistent compilation cache lives.

A full-width train step takes tens of seconds to compile; the cache
lets the next process that builds the same program load it instead.
The cache key includes the directory, so the directory must not move
between runs.
"""
from __future__ import annotations

import os

import jax

CHECKOUT_CACHE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its directory:
    ``$JAX_COMPILATION_CACHE_DIR`` where that is set, else ``.jax_cache``
    at the root of the checkout.  Call before the first compile."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or CHECKOUT_CACHE
    jax.config.update("jax_compilation_cache_dir", path)
    return path
