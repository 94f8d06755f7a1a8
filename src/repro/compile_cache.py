"""JAX's persistent compilation cache, at one fixed place.

Entry points that compile for a device (`chip_smoke.py`,
`benchmarks/run.py`, `python -m repro.api`, `python -m repro.farm
worker`) call `enable_compile_cache()` once at start-up; importing this
module changes nothing.  The cache path is part of every entry's key, so
it is fixed — `<repo>/.jax_cache`, never a temp dir, pid or timestamp —
and a directory named by `JAX_COMPILATION_CACHE_DIR` wins untouched.
"""
from __future__ import annotations

import os

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DEFAULT_DIR = os.path.join(REPO_ROOT, ".jax_cache")


def enable_compile_cache() -> str:
    """Point JAX's compilation cache at `JAX_COMPILATION_CACHE_DIR` when
    it is set (JAX reads it itself), else at `<repo>/.jax_cache`.
    Returns the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
