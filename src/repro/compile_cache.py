"""JAX's persistent compilation cache, set up in one place.

Entry points that run on an accelerator (``chip_smoke.py``, the
``benchmarks/`` mains) call :func:`enable_compile_cache` before their first
compile, so a second run of the same programs loads executables from disk
instead of compiling them again:

* where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it as the
  cache directory, and no other directory is set here;
* otherwise the cache lives at a fixed ``.jax_cache/`` under the checkout
  root (the directory is part of what the cache hits on, so it must not
  move between runs; ``.gitignore`` lists it).

Tests never call this: under the test runner every compile stays in memory.
"""

from __future__ import annotations

import os
from typing import Optional

import jax

__all__ = ["CACHE_ENV", "enable_compile_cache"]

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"

# src/repro/compile_cache.py -> the checkout root.
_CHECKOUT_ROOT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)


def enable_compile_cache(root: Optional[str] = None) -> str:
    """Turns on JAX's persistent compilation cache and returns its directory.

    ``root`` overrides the checkout root for the fallback ``.jax_cache/``
    (tests use it); it is ignored when ``JAX_COMPILATION_CACHE_DIR`` is
    set.  Every program is cached, however quickly it compiled, so a warm
    run's compile seconds show the hits."""
    path = os.environ.get(CACHE_ENV)
    if not path:
        path = os.path.join(root or _CHECKOUT_ROOT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path
