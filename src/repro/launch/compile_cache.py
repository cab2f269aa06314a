"""JAX's persistent compilation cache, set from one place.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads that directory
itself and no other is set here.  Otherwise the cache is the fixed
``.jax_cache/`` at the root of the checkout: the directory is part of
what a cached program is found under, so it must not move between runs.
"""

from __future__ import annotations

import os
from pathlib import Path

__all__ = ["ENV_VAR", "compile_cache_dir", "enable_compile_cache"]

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
REPO_ROOT = Path(__file__).resolve().parents[3]


def compile_cache_dir() -> Path:
    """The directory the persistent compilation cache lives in."""
    env = os.environ.get(ENV_VAR)
    return Path(env) if env else REPO_ROOT / ".jax_cache"


def enable_compile_cache() -> Path:
    """Point JAX's persistent compilation cache at
    :func:`compile_cache_dir` and return that directory."""
    import jax
    path = compile_cache_dir()
    jax.config.update("jax_compilation_cache_dir", str(path))
    return path
