"""JAX's persistent compilation cache, placed from outside or at a fixed
path inside the checkout.

``JAX_COMPILATION_CACHE_DIR``, when set, names the directory and nothing
here overrides it.  Otherwise the cache lives in ``.jax_cache/`` at the
root of the checkout, a path derived from this file's location: the
directory is part of the cache key, so a temp name, a pid or a time stamp
would never hit.  Entry points call :func:`enable_compile_cache` before
their first compile; library code and tests do not.
"""
from __future__ import annotations

import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_ROOT = Path(__file__).resolve().parents[3]


def compile_cache_dir() -> str:
    """The cache directory: ``$JAX_COMPILATION_CACHE_DIR`` or the fixed
    in-checkout default."""
    return os.environ.get(ENV_VAR) or str(CHECKOUT_ROOT / ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent cache on for this process; returns the
    directory.  Every compile is cached (no minimum compile time), so a
    second run of an entry point reuses even the small kernel programs."""
    import jax

    path = compile_cache_dir()
    if not os.environ.get(ENV_VAR):  # set: JAX already reads it
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
