"""Where JAX keeps its persistent compilation cache.

A later run finds what an earlier one cached only if the directory does
not move between them: it is either what ``JAX_COMPILATION_CACHE_DIR``
names (JAX reads that variable itself, so nothing else is set) or the
fixed, git-ignored ``.jax_cache`` at the root of the checkout.
"""
from __future__ import annotations

import os
import pathlib

import jax

REPO_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its
    directory (call before the first compile)."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
