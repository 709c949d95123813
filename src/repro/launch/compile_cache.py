"""Where JAX keeps its persistent compilation cache.

Entry points call :func:`enable` once, in ``main``; importing this module
touches no JAX state. The cache key includes the directory, so the path
is fixed: ``JAX_COMPILATION_CACHE_DIR`` when the environment sets it
(JAX reads that variable itself, and no other path is set then), else
``<repo>/.jax_cache``. A repeated run of the same command on the same
machine then loads its programs instead of compiling them again.
"""
from __future__ import annotations

import os

import jax

REPO_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def enable() -> str:
    """Turn the persistent compilation cache on; return its directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if path:
        return path
    jax.config.update("jax_compilation_cache_dir", REPO_CACHE_DIR)
    return REPO_CACHE_DIR
