"""Persistent compilation cache for the entry points.

Called from ``main`` of ``chip_smoke.py``, ``repro.launch.serve`` and
``benchmarks.run``, never at import: importing ``repro`` leaves JAX's cache
settings alone.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

#: The repository root (``src/repro/launch/cache.py`` → three levels up).
REPO_ROOT = Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is honoured as it is (JAX
    reads it itself) and no other directory is set. Otherwise the cache
    lives at the fixed, git-ignored ``<repo>/.jax_cache``: the path is part
    of every entry's key, so it must not move between runs.
    """
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(REPO_ROOT / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path
