"""Persistent compilation cache at a fixed place.

JAX keys its persistent cache by program *and* directory, so a cache only
pays if the directory is stable across runs.  Entry points that compile the
sort at full size (``chip_smoke.py``, ``bench/run.py``) call
:func:`enable_compile_cache` once at start-up; importing ``repro`` never
touches the cache.
"""
from __future__ import annotations

import os

import jax

#: the checkout's own cache directory (git-ignored)
REPO_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; return its directory.

    A set ``JAX_COMPILATION_CACHE_DIR`` wins: JAX reads it itself and no
    other directory is configured.  Otherwise the cache lives in the
    checkout's ``.jax_cache/`` — never a temporary, per-process or dated
    name, so a later run in the same checkout finds it again.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", REPO_CACHE_DIR)
    return REPO_CACHE_DIR
