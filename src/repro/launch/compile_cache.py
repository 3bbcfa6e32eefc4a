"""Where the entry points keep JAX's persistent compile cache.

Called from each entry point's ``__main__`` (never at library import, and
never by the tests), before the first compile.  An environment that sets
``JAX_COMPILATION_CACHE_DIR`` owns the cache and JAX reads that variable
itself; otherwise the cache lives at a fixed ``.jax_cache/`` at the repo
root.  The path is part of each entry's key, so it never names a temporary
directory, a pid or a time: a second run finds the first run's programs.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn the persistent compile cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
