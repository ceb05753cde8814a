"""JAX's persistent compilation cache at a stable path.

The cache key includes the cache directory, so a directory that moves
between runs never hits.  ``JAX_COMPILATION_CACHE_DIR``, when set, wins:
JAX reads it itself and nothing here overrides it.  Otherwise the cache
lives at ``<checkout>/.jax_cache`` (listed in ``.gitignore``).
"""

from __future__ import annotations

import os

import jax

#: Fixed in-checkout cache path used when the environment names none.
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))),
    ".jax_cache",
)


def configure_compile_cache() -> str:
    """Enable the persistent compile cache; returns its directory.  Call
    before the first compile of the process."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
