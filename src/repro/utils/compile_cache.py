"""JAX's persistent compilation cache for this checkout's entry points.

A cold process on the chip compiles every program it runs; the
persistent cache lets the next process load them instead.  Its path is
part of what it stores, so it must not move between runs: the cache
lives where ``JAX_COMPILATION_CACHE_DIR`` says when that is set (JAX
reads the variable itself), and otherwise at ``<checkout>/.jax_cache``.

Call :func:`enable_compile_cache` from a script's ``__main__`` block,
never at import and never from tests.
"""
from __future__ import annotations

import os
from pathlib import Path

CHECKOUT = Path(__file__).resolve().parents[3]
ENV = "JAX_COMPILATION_CACHE_DIR"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on for this process and
    return its directory."""
    import jax

    path = os.environ.get(ENV)
    if not path:
        path = str(CHECKOUT / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    # cache every program, however fast it compiled: a chip run compiles
    # dozens of small kernels and scatter buckets
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path
