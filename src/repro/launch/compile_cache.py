"""JAX's persistent compilation cache, at one fixed path.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
nothing here changes it.  Otherwise the cache lives at ``.jax_cache/``
in the checkout (gitignored): a fixed path, because the path is part of
the cache key and a directory that moves never hits.  Entry points call
``enable()``; library code and tests never do.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable() -> str:
    """Turn the cache on; returns the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
