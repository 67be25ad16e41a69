"""Where JAX keeps its persistent compilation cache for this repo's runs.

The cache is keyed by its path as well as by the program, so it lives at
one fixed place: the directory ``JAX_COMPILATION_CACHE_DIR`` names when it
is set (JAX reads that variable itself, so nothing is set in code), and
``<repo>/.jax_cache`` otherwise (git-ignored).
"""

from __future__ import annotations

import os

__all__ = ["REPO_CACHE_DIR", "enable_compile_cache"]

REPO_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its fixed directory and
    return that directory.  Call before the first compile: JAX decides once
    per process whether the cache is in use."""
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    import jax

    jax.config.update("jax_compilation_cache_dir", REPO_CACHE_DIR)
    return REPO_CACHE_DIR
