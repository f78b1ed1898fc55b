"""Persistent XLA compilation cache, placeable from outside.

`JAX_COMPILATION_CACHE_DIR` wins when set: JAX reads it itself and this
module sets nothing. Otherwise the cache goes to `.jax_cache` at the
root of the checkout — a fixed path (the path is part of a cache
entry's key, so a directory that moves never hits), listed in
`.gitignore`.
"""
from __future__ import annotations

import os
from pathlib import Path

CHECKOUT = Path(__file__).resolve().parents[3]


def use_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory;
    returns the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
