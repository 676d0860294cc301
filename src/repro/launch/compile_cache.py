"""JAX's persistent compilation cache for the chip entry points.

``chip_smoke.py``, ``repro.launch.serve`` and ``repro.launch.train`` call
:func:`enable_compile_cache` before they compile anything.  The cache lives
where ``JAX_COMPILATION_CACHE_DIR`` says when it is set (JAX reads that
variable itself), else at the fixed ``<repo>/.jax_cache`` — never a
temporary, pid- or time-based path, since the directory is part of what a
later run must find again.  Tests enable no cache.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["REPO_CACHE_DIR", "enable_compile_cache"]

REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory.

    Every compile is cached (no minimum compile time): the Pallas kernels
    compile in about a second each, and a warm run should skip them too.
    """
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(REPO_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
