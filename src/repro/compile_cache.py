"""Persistent XLA compile cache for the entry points.

Entry points (``repro.launch.walk``, ``repro.launch.serve_walks``,
``chip_smoke.py``) call :func:`enable_compile_cache` once at start-up;
library code never does, so importing ``repro`` changes no JAX setting.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: the checkout root (``src/repro/compile_cache.py`` → two levels up)
CHECKOUT_ROOT = Path(__file__).resolve().parents[2]


def enable_compile_cache() -> str:
    """Keep compiled executables in ``JAX_COMPILATION_CACHE_DIR`` when
    that is set, else in the git-ignored ``.jax_cache/`` at the checkout
    root — a fixed path, so a later run finds them again.  Returns the
    directory in use."""
    path = (os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or str(CHECKOUT_ROOT / ".jax_cache"))
    jax.config.update("jax_compilation_cache_dir", path)
    return path
