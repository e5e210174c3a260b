"""The one place that decides where JAX's persistent compile cache lives.

Every entry point that compiles for a device calls
:func:`enable_compile_cache` before its first compile (``chip_smoke.py``,
``bench.py``, ``bench_infer.py``, the ``tools/*bench*.py`` entries,
``app/main.py``).  The rule:

  * ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads the variable itself;
    this module leaves it alone and sets no other directory in code.
  * unset: one FIXED directory inside the checkout, ``.jax_cache/``
    (git-ignored).  The path is part of what makes a cache findable
    again, so it is never a temporary name, a pid or a time.
"""
from __future__ import annotations

import os
from pathlib import Path

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_CACHE_DIR = Path(__file__).resolve().parent.parent / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compile cache on; returns the directory in
    use.  Idempotent; touches no device."""
    placed = os.environ.get(CACHE_ENV)
    if placed:
        return placed
    import jax

    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)
