"""JAX's persistent compilation cache for the programs that drive the chip.

``enable_compile_cache()`` is called by ``chip_smoke.py`` and the benchmark
entry points, never at library import: a library that picks a cache
directory for its caller would override the caller's own choice.
"""
from __future__ import annotations

import os
from pathlib import Path

# the checkout root (this file is <root>/src/repro/compile_cache.py)
_ROOT = Path(__file__).resolve().parents[2]


def cache_dir(environ=os.environ) -> str:
    """Where compiled programs are cached: ``JAX_COMPILATION_CACHE_DIR``
    when it is set (JAX reads it itself), else the checkout's fixed
    ``.jax_cache`` — a stable path, so a later run finds what an earlier
    one compiled."""
    return environ.get("JAX_COMPILATION_CACHE_DIR") or str(_ROOT / ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent cache on and return its directory."""
    import jax
    path = cache_dir()
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", path)
    return path
