"""Where JAX keeps its persistent compilation cache — decided once.

A cold B1 serving run compiles one executor per (bucket, precision);
the persistent cache lets the next process on the same machine skip
that.  The cache key includes the directory, so the directory must not
move between runs: never a temp name, a process id or a time stamp.

``use_compile_cache()`` is the one helper that entry points
(``chip_smoke.py``, the benchmarks) call before their first compile.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

# <checkout>/.jax_cache (listed in .gitignore)
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Point JAX's persistent compile cache at its one directory and
    return it.  ``JAX_COMPILATION_CACHE_DIR``, when set, is the
    directory: JAX reads it itself and nothing is set here.  Otherwise
    the cache lives at ``<checkout>/.jax_cache``."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
