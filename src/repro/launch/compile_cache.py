"""JAX's persistent compilation cache for the entry points.

A 32-layer round program takes about a minute to compile; with the cache a
second process (or a second build of the same program in one process)
loads it from disk instead.  The path is fixed, so a later process finds
what an earlier one stored: ``.jax_cache/`` at the root of the checkout,
never a temp name, a process id or a time.

The key holds each instruction's metadata: its ``op_name``, which carries the
program's ``round.*`` layer scopes (``repro.obs``), and its source file and
line.  JAX leaves the metadata out of the key by default, and then a program
that differs from a cached one only there runs the cached executable, whose
profile shows the old names.  Source files are recorded relative to the
checkout, so a checkout at another path finds the same entries.
"""
from __future__ import annotations

import os
import re

import jax

__all__ = ["CACHE_DIR", "use_compile_cache"]

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
CACHE_DIR = os.path.join(ROOT, ".jax_cache")


def use_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX has already read it and
    its setting is left alone; otherwise the cache goes to ``CACHE_DIR``."""
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    jax.config.update("jax_hlo_source_file_canonicalization_regex",
                      "^" + re.escape(ROOT + os.sep))
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    return CACHE_DIR
