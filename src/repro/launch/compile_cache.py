"""JAX's persistent compilation cache for the entry points.

Entry points (serving, training, the benchmark mains, ``chip_smoke.py``)
call :func:`enable_compile_cache` first thing in their ``main``; nothing
calls it at import time.  Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX
already reads it and this leaves the setting alone.  Otherwise the cache
goes to ``.jax_cache`` at the root of the checkout: a fixed path, because
the path is part of what a later run must find again.
"""

from __future__ import annotations

import os
import pathlib

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
# src/repro/launch/compile_cache.py -> the checkout root
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and
    return that directory."""
    if os.environ.get(ENV_VAR):
        return os.environ[ENV_VAR]
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
