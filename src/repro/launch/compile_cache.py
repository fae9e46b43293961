"""Where the entry points keep JAX's persistent compilation cache.

``JAX_COMPILATION_CACHE_DIR``, when set, is honoured as it stands (JAX
reads it itself). Otherwise the cache goes to ``<checkout>/.jax_cache``:
one fixed path, because the path is part of what a later run must find
again, and inside the checkout, which is the only place the program
writes. The directory is git-ignored.
"""

from __future__ import annotations

import os

import jax

CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))


def use_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory; returns
    the directory. Call before the first compile."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = os.path.join(CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
