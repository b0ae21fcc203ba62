"""JAX's persistent compilation cache for the repo's entry points.

``chip_smoke.py``, ``benchmarks/run.py`` and the gated ``benchmarks/bench_*``
scripts call :func:`enable_compile_cache` at the top of their ``main()``, so
repeated runs on one machine reuse compiled placement kernels.  Importing
this module changes nothing: ``repro`` never configures JAX as it is
imported.
"""
from __future__ import annotations

import os
from pathlib import Path

__all__ = ["CHECKOUT_CACHE_DIR", "compile_cache_dir", "enable_compile_cache"]

# Fixed so that every run from this checkout hits the same entries (the
# cache directory is part of what a later run must find again).
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def compile_cache_dir() -> str:
    """``$JAX_COMPILATION_CACHE_DIR`` when set, else ``<checkout>/.jax_cache``."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(CHECKOUT_CACHE_DIR)


def enable_compile_cache() -> str:
    """Point JAX's persistent cache at :func:`compile_cache_dir` and cache
    every compilation, however short: some placement kernels compile in
    less than JAX's default threshold of one second.  Returns the
    directory."""
    import jax

    path = compile_cache_dir()
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
