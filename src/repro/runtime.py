"""Where the program keeps its on-disk caches.

Both caches live at fixed paths inside the checkout (gitignored), never
under a temp name, a pid or a time: JAX's persistent compile cache keys
on the path, so a directory that moves never hits, and a plan must come
from what the checkout holds, not from a stray file in a home directory.
"""
from __future__ import annotations

import os
import pathlib

REPO_ROOT = pathlib.Path(__file__).resolve().parents[2]


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its path.

    Where `JAX_COMPILATION_CACHE_DIR` is set, JAX already reads it and
    this sets nothing; otherwise the cache is `<checkout>/.jax_cache/`.
    Call before the first compile."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    path = str(REPO_ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
