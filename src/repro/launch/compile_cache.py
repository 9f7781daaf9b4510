"""JAX's persistent compilation cache for the entry points.

A 32-layer round program takes about a minute to compile; with the cache a
second process (or a second build of the same program in one process)
loads it from disk instead.  The path is fixed, so a later process finds
what an earlier one stored: ``.jax_cache/`` at the root of the checkout,
never a temp name, a process id or a time.
"""
from __future__ import annotations

import os

import jax

__all__ = ["CACHE_DIR", "use_compile_cache"]

CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))),
    ".jax_cache",
)


def use_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX has already read it and
    its setting is left alone; otherwise the cache goes to ``CACHE_DIR``."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    return CACHE_DIR
