"""JAX persistent compilation cache for the command-line entry points.

Entry points call ``enable()`` first thing in ``main()``; importing this
module changes nothing.  The cache key includes the cache directory, so the
directory must not move between runs:

  * ``JAX_COMPILATION_CACHE_DIR`` set -> that directory, and no other;
  * unset -> ``<checkout>/.jax_cache`` (listed in ``.gitignore``).
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

# src/repro/runtime/compile_cache.py -> the checkout root.
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable() -> str:
    """Point JAX's persistent compilation cache at
    ``$JAX_COMPILATION_CACHE_DIR``, else ``DEFAULT_DIR``; return it."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(DEFAULT_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
