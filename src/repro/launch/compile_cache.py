"""JAX's persistent compilation cache, placed from outside or at a fixed path.

Call ``enable_compile_cache()`` once, before the first compile.  Nothing
happens at import.

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself; no other
  directory is set here.
* Not set: the cache goes to ``<repo>/.jax_cache`` (gitignored).  The path
  is fixed because it is part of the cache's key: a directory that moved
  between runs would never hit.
"""
from __future__ import annotations

import os
import pathlib

REPO_ROOT = pathlib.Path(__file__).resolve().parents[3]
DEFAULT_DIR = REPO_ROOT / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compile cache; returns its directory."""
    import jax

    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
