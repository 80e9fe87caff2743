"""JAX's persistent compilation cache, kept at one fixed place.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
this module sets nothing.  Otherwise the cache goes to ``.jax_cache/``
at the root of the checkout.  The path is part of every cache key, so
it is never built from a temporary name, a process id or the time.
The tests leave the cache off.
"""

from __future__ import annotations

import os
import pathlib

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
REPO_ROOT = pathlib.Path(__file__).resolve().parents[3]


def cache_dir() -> str:
    """The directory the cache uses: the variable's value, else
    ``<repo>/.jax_cache``."""
    return os.environ.get(ENV_VAR) or str(REPO_ROOT / ".jax_cache")


def enable() -> str:
    """Turn the persistent cache on; returns its directory."""
    path = cache_dir()
    if not os.environ.get(ENV_VAR):
        jax.config.update("jax_compilation_cache_dir", path)
    return path
