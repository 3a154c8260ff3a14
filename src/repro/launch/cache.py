"""JAX's persistent compilation cache, at one fixed place per checkout.

`enable_compile_cache` is called by every entry point that compiles for
the chip (``chip_smoke.py``, `repro.launch.serve`, the benchmark
scripts) before its first compile.  Where ``JAX_COMPILATION_CACHE_DIR``
is set, that directory is the cache and no other is set.  Otherwise the
cache is ``<checkout>/.jax_cache`` (listed in ``.gitignore``): a fixed
path, because the path is part of the cache key — a directory derived
from a temp dir, a PID or the time would never hit.
"""
from __future__ import annotations

import os
import pathlib

import jax

__all__ = ["CHECKOUT", "enable_compile_cache"]

#: The checkout root: this file is ``<checkout>/src/repro/launch/cache.py``.
CHECKOUT = pathlib.Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory.

    Returns the directory in use.
    """
    path = (os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or str(CHECKOUT / ".jax_cache"))
    jax.config.update("jax_compilation_cache_dir", path)
    return path
