"""JAX's persistent compilation cache for the repo's entry points.

A 32-layer serving program compiles in seconds; a run compiles dozens of
them.  The persistent cache lets the next process on the same machine
load them instead.  Its directory is part of what the cache is keyed on,
so it is fixed: ``JAX_COMPILATION_CACHE_DIR`` where that is set (JAX reads
it itself, and nothing here overrides it), otherwise ``.jax_cache/`` at
the root of the checkout (git-ignored).  Call ``enable_compile_cache``
before the first compile.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point the persistent compilation cache at its fixed directory and
    return that directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
