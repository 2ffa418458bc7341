"""Where JAX keeps its persistent compilation cache.

Every entry point calls :func:`enable_compile_cache` before it compiles:

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself and the cache
  stays there; nothing here overrides it.
* unset: the cache goes to :data:`CHECKOUT_CACHE_DIR`, a fixed directory
  inside the checkout (listed in ``.gitignore``).  The path is part of
  every entry's key, so a fixed path lets later processes on the same
  machine hit what earlier ones compiled.
"""
from __future__ import annotations

import os

import jax

#: The cache directory used when ``JAX_COMPILATION_CACHE_DIR`` is unset.
CHECKOUT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", CHECKOUT_CACHE_DIR)
    return CHECKOUT_CACHE_DIR
