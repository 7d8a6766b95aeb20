"""JAX's persistent compilation cache for the entry points.

A cold run on the chip compiles every program; the persistent cache lets
the next process (or a second scheduler over the same shapes in the same
process) load them instead.  The cache directory is part of the cache's
key, so it is a fixed path: ``JAX_COMPILATION_CACHE_DIR`` when the
environment sets it (JAX reads the variable itself and this module sets
nothing), else ``<checkout>/.jax_cache``, which ``.gitignore`` lists.
"""
from __future__ import annotations

import os

import jax

__all__ = ["CACHE_DIR", "enable_compile_cache"]

#: the fallback cache directory: ``.jax_cache`` at the checkout's root
CACHE_DIR = os.path.abspath(os.path.join(os.path.dirname(__file__),
                                         "..", "..", "..", ".jax_cache"))


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its fixed directory and
    return that directory.  Call before the first compile."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    return CACHE_DIR
