"""Where the entry points keep JAX's persistent compilation cache.

Called by ``chip_smoke.py`` and the ``serve``/``train`` mains, never at
import.  The path is part of what makes a cache entry found again, so it is
one fixed directory: ``JAX_COMPILATION_CACHE_DIR`` where it is set (JAX
reads that variable itself, and nothing else is set here), otherwise
``.jax_cache`` at the root of the checkout (git-ignored).
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory:
    the environment's ``JAX_COMPILATION_CACHE_DIR`` if set, else
    :data:`CHECKOUT_CACHE`."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
