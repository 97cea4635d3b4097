"""Placement of JAX's persistent compilation cache for the repo's scripts.

Scripts that drive the chip call :func:`use_compile_cache` first thing;
importing ``repro`` alone never touches the cache.  The cache key includes
its path, so the directory is fixed: ``JAX_COMPILATION_CACHE_DIR`` when the
environment sets it (JAX reads that variable itself, and nothing else is
set here), else ``<repo>/.jax_cache``.
"""
from __future__ import annotations

import os

import jax


def use_compile_cache(repo_root: str) -> str:
    """Return the cache directory, pointing JAX at ``<repo_root>/.jax_cache``
    unless ``JAX_COMPILATION_CACHE_DIR`` is set."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(os.path.abspath(repo_root), ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path
