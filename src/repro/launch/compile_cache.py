"""JAX's persistent compilation cache for the launch entry points.

A full-width step program takes tens of seconds to compile on a TPU; the
persistent cache lets the next process reuse it. The cache directory is
part of each entry's lookup, so it must not move between runs: it is
``JAX_COMPILATION_CACHE_DIR`` where that is set (JAX reads the variable
itself), and otherwise ``.jax_cache`` at the root of this checkout.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn the persistent cache on before the first compile; returns its
    directory.

    Every program is kept, whatever its compile time: JAX's default keeps
    only those that took a second or more, and olmo-1b's fused decode step
    compiles in about that long on a v5e host, so it was kept in some runs
    and not in others."""
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
