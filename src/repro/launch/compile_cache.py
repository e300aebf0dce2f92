"""JAX's persistent compilation cache for the launchers.

``enable_compile_cache()`` is called by each entry point before its first
compile, never at import.  Where ``JAX_COMPILATION_CACHE_DIR`` is set,
JAX reads it itself and it is left alone; otherwise the cache is kept in
``.jax_cache/`` at the root of the checkout.  The path is fixed because it
is part of the cache key: a directory that moves never hits.  Programs
that compile in 0.1 s or more are cached, unless
``JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS`` says otherwise.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory it writes to."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    if not os.environ.get("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"):
        # the decode step and the AES kernel compile in under a second on
        # the chip, below JAX's default floor of one; eager ops stay out
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.1)
    return jax.config.jax_compilation_cache_dir
