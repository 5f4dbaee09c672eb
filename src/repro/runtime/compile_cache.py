"""Where JAX keeps its persistent compilation cache for this checkout.

A cold process on a fresh machine compiles every plan, stage segment and
kernel again; the persistent cache lets a second run in the same checkout
load them instead.  Entry points (``chip_smoke.py``, the examples,
``benchmarks/run.py``) call :func:`enable_compile_cache` once at start-up;
importing :mod:`repro` never does.
"""

from __future__ import annotations

import os
from pathlib import Path

ENV_DIR = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn JAX's persistent compilation cache on; return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, the cache is there and no
    other directory is set in code.  Otherwise it is the fixed
    ``<checkout>/.jax_cache`` — fixed, because the path is part of what a
    later run must find again.
    """
    import jax

    path = os.environ.get(ENV_DIR) or str(CHECKOUT_CACHE)
    jax.config.update("jax_enable_compilation_cache", True)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
