"""Where JAX's persistent compilation cache lives.

Entry points (``chip_smoke.py``, ``repro.launch.serve``,
``benchmarks/run.py``) call ``enable_compile_cache()`` once at start-up;
library modules never touch the setting.  The directory is part of each
cache entry's key, so it is a fixed path, never a temp name, pid or time.
"""
from __future__ import annotations

import os
import pathlib

import jax

__all__ = ["enable_compile_cache", "REPO_CACHE_DIR"]

# <repo>/.jax_cache — listed in .gitignore
REPO_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, wins: JAX reads it itself and
    no other directory is set here.  Otherwise the cache goes to the fixed
    in-repo ``.jax_cache``."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
