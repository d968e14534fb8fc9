"""Where JAX keeps its persistent compilation cache.

Compiled programs are cached under the path ``JAX_COMPILATION_CACHE_DIR``
names, when the environment sets it; JAX reads that variable itself, so
nothing is changed then.  Otherwise the cache sits at one fixed path
inside the checkout, ``<repo>/.jax_cache`` (git-ignored): the path is
part of the cache key, so a directory that moved would never hit.

Call :func:`use_compile_cache` from an entry point, before the first
compile; importing this module changes nothing.
"""
from __future__ import annotations

import os
import pathlib

import jax

REPO_CACHE = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn the persistent cache on and return its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE))
    return str(REPO_CACHE)
