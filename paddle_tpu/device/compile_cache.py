"""Placement of jax's persistent compilation cache.

Entry points (``chip_smoke.py``, the bench legs, ``bench_serve.py``,
``python -m paddle_tpu.serving``, ``__graft_entry__.py``) call
:func:`place_compile_cache` before their first compile; nothing calls it
at import time.  The operator moves the cache with
``JAX_COMPILATION_CACHE_DIR`` — jax reads that variable itself, so when
it is set this module sets no directory in code.  Otherwise the cache
lives at ``<checkout>/.jax_cache``: a fixed path, because the directory
is part of the cache key and one that moves (a temp dir, a pid, the
clock) never hits.
"""
from __future__ import annotations

import os

__all__ = ["place_compile_cache", "DEFAULT_CACHE_DIR"]

DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def place_compile_cache() -> str:
    """Point jax's persistent compile cache at its directory; returns
    the directory in effect."""
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    import jax
    jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    return DEFAULT_CACHE_DIR
