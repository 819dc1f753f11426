"""Where JAX's persistent compilation cache lives.

A cold run of a full-width model compiles every step program; the cache
lets later processes on the same machine load them instead.  The cache key
includes the directory, so it has to stay put between runs.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

# <repo>/.jax_cache (this file is <repo>/src/repro/runtime/compile_cache.py)
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache(default_dir: str | os.PathLike = DEFAULT_DIR) -> str:
    """Turn the persistent compilation cache on and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX has already taken it
    from the environment and nothing else is set here.  Otherwise the
    cache goes to ``default_dir``, the git-ignored ``.jax_cache/`` at the
    repository root.  Entry points call this at the top of ``main``; it is
    never called at import, so importing the package leaves JAX's
    configuration as it was.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(default_dir)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
