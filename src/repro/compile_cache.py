"""Persistent compilation cache at a place the caller can choose.

Entry points (``chip_smoke.py``, ``benchmarks/run.py``,
``examples/train_lm.py``, ``repro.launch.train``) call
:func:`enable_compile_cache` before their first compile; the library
never does, at import or elsewhere.  When ``JAX_COMPILATION_CACHE_DIR``
is set, JAX already reads it and nothing is changed.  Otherwise the
cache lives at ``<checkout>/.jax_cache``: a fixed path, because the path
is part of what a later run must find again.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["DEFAULT_CACHE_DIR", "enable_compile_cache"]

#: ``<checkout>/.jax_cache`` (this file is ``<checkout>/src/repro/``).
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)
