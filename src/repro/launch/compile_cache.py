"""Where JAX keeps its persistent compilation cache.

Entry points call :func:`enable_compile_cache` once, before they compile
anything; library code never does.  A cache's key includes its path, so
the path must not move between runs: no temp directory, pid or time.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

# <checkout>/.jax_cache, next to src/ (git-ignored)
CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX's own setting stands
    and nothing here sets another directory.  Otherwise the cache lives
    at the fixed ``CACHE_DIR`` inside the checkout.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
