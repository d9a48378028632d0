"""Where JAX keeps its persistent compilation cache.

When ``JAX_COMPILATION_CACHE_DIR`` is set (even to ``""``, which turns the
cache off), JAX reads it itself and nothing is set here.  Otherwise the
cache lives in one fixed directory inside the checkout, ``.jax_cache/``
(listed in ``.gitignore``): never a path built from a temp name, a pid or
the time, so every run of the same checkout finds the same entries.

Entry points (``launch/serve.py``, ``benchmarks/run.py``,
``chip_smoke.py``) and ``tests/conftest.py`` call ``enable_compile_cache``
before their first compile.
"""
from __future__ import annotations

import os
from pathlib import Path

ENV = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and
    return that directory ("" when the environment turned it off)."""
    if ENV in os.environ:
        return os.environ[ENV]
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
