"""Where JAX's persistent compilation cache lives.

Entry points (``tc_run``, ``serve``, ``chip_smoke.py``) call
:func:`configure_compile_cache` once, before their first compile.  When
``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and nothing
is set here.  Otherwise the cache goes to ``<checkout>/.jax_cache``: a
fixed path, because the cache directory is part of what makes a later
process find an entry again.  Tests do not call this.
"""
from __future__ import annotations

import os
from pathlib import Path

__all__ = ["compile_cache_dir", "configure_compile_cache"]

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
# src/repro/launch/compile_cache.py -> the checkout root
_CHECKOUT = Path(__file__).resolve().parents[3]


def compile_cache_dir() -> str:
    """The cache directory the entry points use."""
    return os.environ.get(ENV_VAR) or str(_CHECKOUT / ".jax_cache")


def configure_compile_cache() -> str:
    """Point JAX's persistent compilation cache at
    :func:`compile_cache_dir` and return it."""
    path = compile_cache_dir()
    if not os.environ.get(ENV_VAR):
        import jax

        jax.config.update("jax_compilation_cache_dir", path)
    return path
