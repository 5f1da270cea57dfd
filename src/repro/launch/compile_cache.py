"""JAX's persistent compilation cache for the repo's entry points.

Every entry point (``chip_smoke.py``, ``launch/serve.py``,
``launch/train.py``, ``benchmarks/run.py``) calls ``enable_compile_cache``
once, before it compiles anything.  Library code never touches the
cache, so tests and importers keep JAX's own settings.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_ROOT = Path(__file__).resolve().parents[3]
CACHE_DIR = REPO_ROOT / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX has read it itself and
    nothing else is set.  Otherwise the cache goes to ``<repo>/.jax_cache``:
    a fixed path (it is part of what keys the cache), so a later run of the
    same checkout finds what an earlier one compiled."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
