"""Where JAX keeps its persistent compilation cache.

``enable_compile_cache()`` is called by the entry points (``launch.train``,
``launch.serve``, ``chip_smoke.py``) before their first compile — never at
import, never by tests. If ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it
itself and nothing is set here. Otherwise the cache goes to the fixed
``<repo>/.jax_cache`` (git-ignored): the directory is part of the cache key,
so it must not move from run to run.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Make the persistent compilation cache land in one fixed directory;
    returns that directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
