"""JAX's persistent compilation cache, kept where the next run finds it."""
from __future__ import annotations

import os
import pathlib

__all__ = ["CHECKOUT_CACHE_DIR", "enable_compile_cache"]

#: Fixed directory inside the checkout; the path is part of the cache key,
#: so it must not move between runs.
CHECKOUT_CACHE_DIR = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its directory.

    A ``JAX_COMPILATION_CACHE_DIR`` set in the environment is JAX's own
    setting and is left alone; otherwise the cache goes to
    :data:`CHECKOUT_CACHE_DIR`.
    """
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(CHECKOUT_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
