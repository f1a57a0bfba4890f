"""JAX persistent compilation cache location for the repo's entry points.

Where `JAX_COMPILATION_CACHE_DIR` is set, JAX already keeps its cache
there and nothing is changed. Otherwise the cache goes to the fixed path
`<checkout>/.jax_cache`: the path is part of every entry's key, so it must
never move between runs (no temp names, pids or times in it).
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT = Path(__file__).resolve().parents[3]
CACHE_DIR = CHECKOUT / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory; returns
    the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
