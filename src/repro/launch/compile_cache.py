"""Where JAX keeps its persistent compilation cache, and what keys it.

Entry points call :func:`enable_compile_cache` once, before they compile
anything; library code never does.  A cache's key includes its path, so
the path must not move between runs: no temp directory, pid or time.

A program's key also hashes each Pallas kernel's Mosaic body, which
keeps the source locations of the Python frames that traced it.  Those
locations are made relative to the checkout, so the same commit keys
the same programs wherever it is checked out.
"""

from __future__ import annotations

import os
import re
from pathlib import Path

import jax

# <checkout>/.jax_cache, next to src/ (git-ignored)
ROOT = Path(__file__).resolve().parents[3]
CACHE_DIR = ROOT / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX's own setting stands
    and nothing here sets another directory.  Otherwise the cache lives
    at the fixed ``CACHE_DIR`` inside the checkout.  Either way, source
    locations drop the checkout's own path.
    """
    jax.config.update("jax_hlo_source_file_canonicalization_regex",
                      "^" + re.escape(str(ROOT) + os.sep))
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
