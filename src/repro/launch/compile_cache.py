"""Persistent XLA compilation cache for the entry points.

One rule for every program of the repo (``chip_smoke.py``,
``python -m repro.launch.serve``, ``python -m benchmarks.run``): where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing is
set here; otherwise the cache lives at the fixed ``<repo>/.jax_cache``
(listed in ``.gitignore``).  The path is part of the cache key, so it never
depends on a temp dir, a pid or the time.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE))
    return str(REPO_CACHE)
