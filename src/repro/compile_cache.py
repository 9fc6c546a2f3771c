"""Where JAX keeps its persistent compilation cache.

JAX reads ``JAX_COMPILATION_CACHE_DIR`` itself; where that is set, this
module sets nothing.  Where it is unset, :func:`use_compile_cache` puts the
cache in one fixed directory inside the checkout, ``<repo>/.jax_cache``
(listed in ``.gitignore``).  The directory never carries a temporary name,
a process id or a time: a cache that moves between runs is never hit.

Entry points that compile device programs (``chip_smoke.py``, the
benchmarks) call :func:`use_compile_cache` once, before their first
compile.  Importing this module changes nothing.
"""
from __future__ import annotations

import os

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
REPO_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
        __file__)))), ".jax_cache")


def use_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    configured = os.environ.get(ENV_VAR)
    if configured:
        return configured
    import jax
    jax.config.update("jax_compilation_cache_dir", REPO_CACHE_DIR)
    return REPO_CACHE_DIR
