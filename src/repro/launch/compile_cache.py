"""Where the launchers keep JAX's persistent compilation cache.

``JAX_COMPILATION_CACHE_DIR``, when set, is read by JAX itself and wins:
nothing else is set.  Otherwise the cache goes to one fixed directory in
the checkout, ``<repo>/.jax_cache`` (gitignored).  The path takes part in
the cache's key, so it is never built from a temp name, a process id or
the time: a directory that moves never hits.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def place_compile_cache() -> str:
    """Point JAX's compilation cache at its directory; returns the path."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
