"""Where the entry points keep JAX's persistent compilation cache.

A cold call on a fresh machine compiles every program; the persistent cache
lets a second run of the same programs skip XLA's compile. The cache key
includes its directory, so the directory must not move between runs: it is
either the one ``JAX_COMPILATION_CACHE_DIR`` names, or a fixed directory
inside the checkout (listed in ``.gitignore``).
"""
from __future__ import annotations

import os

import jax

#: The in-checkout cache directory used when ``JAX_COMPILATION_CACHE_DIR``
#: is not set.
CACHE_DIR = os.path.abspath(os.path.join(
    os.path.dirname(__file__), "..", "..", "..", ".jax_cache"))


def use_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory.

    When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
    sets nothing. Otherwise the cache goes to :data:`CACHE_DIR`. Call it
    before the first compile.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    return CACHE_DIR
