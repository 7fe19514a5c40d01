"""JAX's persistent compilation cache, placed from outside or at a fixed
path inside the checkout.

The one place in the program that sets a cache directory. Entry points
(``chip_smoke.py``, ``benchmarks/run.py``) call :func:`enable` before their
first compile:

  * ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself and this code
    sets nothing;
  * unset: the cache goes to ``<checkout>/.jax_cache`` (git-ignored). The
    path is fixed — never a temporary directory, a pid or a time — because
    it is part of the cache key: a directory that moves never hits.
"""
from __future__ import annotations

import os

CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def enable() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    import jax

    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    return CACHE_DIR
