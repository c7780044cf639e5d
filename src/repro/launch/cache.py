"""Where JAX keeps its persistent compilation cache for this repository.

Entry points call :func:`use_compile_cache` from ``main``, never at import
time.  The cache's key includes its directory, so the directory is fixed:
``JAX_COMPILATION_CACHE_DIR`` when it is set (JAX reads it itself), else
``.jax_cache`` at the root of the checkout (git-ignored).
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Point JAX's compilation cache at its fixed directory; return it."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)
