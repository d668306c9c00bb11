"""JAX's persistent compilation cache for the command-line entry points.

Called from the ``main()`` of ``serve``, ``train`` and ``tune`` and from
``chip_smoke.py`` — never when a library module is imported, so tests
and library users keep whatever cache setting they chose.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

# fixed, so the cache key's path part is the same on every run
REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, wins: JAX reads it on its
    own and nothing is changed here. Otherwise the cache is
    ``<repo>/.jax_cache``."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
