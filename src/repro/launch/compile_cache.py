"""Persistent XLA compile cache for the repo's entry points.

``chip_smoke.py``, ``benchmarks/run.py`` and ``python -m
repro.launch.serve`` call :func:`use_checkout_cache` before their first
compile.  A set ``JAX_COMPILATION_CACHE_DIR`` wins and is left alone;
otherwise the cache lives at ``<checkout>/.xla-cache``, a fixed path, so
a later run from the same checkout finds the programs an earlier one
compiled.  Importing the library sets no cache.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

# src/repro/launch/compile_cache.py -> the checkout root
CHECKOUT = Path(__file__).resolve().parents[3]


def use_checkout_cache() -> str:
    """Point JAX's persistent compile cache at the checkout; return the dir."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(CHECKOUT / ".xla-cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
