"""Where JAX's persistent compilation cache lives — decided in one place.

The directory is part of every cache key, so it must not move between
runs.  Whoever starts the process may place it from outside with
``JAX_COMPILATION_CACHE_DIR``; JAX reads that variable itself when it is
imported, and then nothing in this repo sets ``jax_compilation_cache_dir``
in code.  Without the variable the cache is ``<checkout>/.jax_cache``.
"""

from __future__ import annotations

import os

__all__ = ["ENV", "cache_dir", "enable"]

ENV = "JAX_COMPILATION_CACHE_DIR"
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def cache_dir() -> str:
    """``$JAX_COMPILATION_CACHE_DIR`` if set, else the fixed
    ``<checkout>/.jax_cache``."""
    return os.environ.get(ENV) or os.path.join(_CHECKOUT, ".jax_cache")


def enable() -> str:
    """Turn the persistent cache on for this process and return its
    directory.  With the variable set JAX has already taken the path
    from it, so only the unset case touches the config."""
    path = cache_dir()
    if not os.environ.get(ENV):
        import jax

        jax.config.update("jax_compilation_cache_dir", path)
    return path
