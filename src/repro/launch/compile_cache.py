"""JAX's persistent compilation cache, kept at one fixed place.

A cache entry is found again only by a process that looks in the same
directory, so the default path is fixed: `<repo>/.jax_cache` (listed in
`.gitignore`).  Where `JAX_COMPILATION_CACHE_DIR` is set, JAX reads it
itself and this module leaves it alone.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory; returns
    the directory in use.  Call before the first compile."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
