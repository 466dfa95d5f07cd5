"""JAX's persistent compilation cache, placed from outside the program.

Entry points (``chip_smoke.py``, the benchmark CLIs) call
:func:`enable_compile_cache` once at start-up; library modules never do,
so importing ``repro`` changes no JAX setting.

The path is part of what the cache is keyed on, so it is fixed: the
``JAX_COMPILATION_CACHE_DIR`` environment variable when it is set (JAX
reads it itself and this module sets nothing), else ``.jax_cache/`` at
the root of the checkout (git-ignored).
"""

from __future__ import annotations

import os

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    path = os.environ.get(ENV_VAR)
    if not path:
        path = DEFAULT_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    return path
