"""Where JAX keeps its persistent compilation cache.

Every entry point that compiles for the chip (`chip_smoke.py`,
`repro.launch.serve`, `repro.launch.train`, `benchmarks/run.py`) calls
`use_compile_cache` once at start-up.  The cache path is part of what makes
an entry hit, so it is fixed: never built from a temp name, a pid or a time.
"""

from __future__ import annotations

import os
import pathlib

import jax

__all__ = ["REPO_CACHE_DIR", "use_compile_cache"]

# <repo>/.jax_cache (this file is <repo>/src/repro/launch/compile_cache.py);
# listed in .gitignore
REPO_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    nothing is set here; otherwise the cache goes to `REPO_CACHE_DIR`."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
