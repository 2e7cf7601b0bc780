"""JAX's persistent compilation cache, shared by the entry points.

Called from each entry point's ``main``, never at import: importing a module
must not change process-wide JAX configuration.
"""
from __future__ import annotations

import os
import pathlib

import jax

#: The fixed in-checkout directory used when ``JAX_COMPILATION_CACHE_DIR``
#: is unset.  The cache directory is part of what lets a later run find an
#: entry, so it must not move between runs.
REPO_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and no
    other directory is set here; otherwise the cache lives in
    ``<repo>/.jax_cache``."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(REPO_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
