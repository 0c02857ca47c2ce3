"""JAX's persistent compilation cache, switched on in one place.

Entry points (``chip_smoke.py``, ``launch/train.py``, ``benchmarks/run.py``
and the examples) call :func:`enable_compile_cache` first thing in their
``main``; library code and tests never do, and nothing calls it at import.
"""
from __future__ import annotations

import os

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))
# A fixed path: the directory is part of what a cache hit matches, so a
# name built from a pid, a temp dir or the time would never hit again.
DEFAULT_DIR = os.path.join(REPO_ROOT, ".jax_cache")


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it as
    ``jax_compilation_cache_dir`` and this sets nothing. Otherwise the
    cache goes to ``<repo root>/.jax_cache`` (git-ignored)."""
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    import jax
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
