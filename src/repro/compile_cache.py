"""JAX's persistent compilation cache for scripts run from a checkout.

Scripts (``chip_smoke.py``, the benchmarks, the examples) call
:func:`enable` once at start-up; nothing calls it on import or in tests.
Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
sets nothing.  Otherwise the cache sits at ``<checkout>/.jax_cache`` — a
fixed path, since the path is part of what a cached entry is found by.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable() -> str:
    """Point JAX's compilation cache at its directory; return the path."""
    env = os.environ.get(ENV)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
