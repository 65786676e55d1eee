"""JAX's persistent compilation cache at one fixed place per checkout.

Entry points (``chip_smoke.py``, ``benchmarks/run.py``, ``examples/``)
call :func:`enable` once at start-up; library code never does, and
importing this module changes nothing. The cache directory is part of each
entry's key, so it must not move between runs: a temporary, per-process or
time-stamped path would never hit.
"""
from __future__ import annotations

import os

import jax

# <checkout>/.jax_cache — listed in .gitignore
_DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "..", "..", "..", ".jax_cache"
)


def enable() -> str:
    """Turn the persistent compilation cache on; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and no
    other path is set here. Otherwise the cache lives in ``.jax_cache`` at
    the root of the checkout.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = os.path.normpath(_DEFAULT_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
