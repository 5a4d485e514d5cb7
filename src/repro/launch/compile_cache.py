"""JAX's persistent compilation cache, placed from outside or in the
checkout.

Entry points (``chip_smoke.py``, ``repro.launch.serve``, ``bench/run.py``,
``benchmarks/run.py``) call ``enable_compile_cache()`` once, before they
compile anything.  Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads
it itself and nothing is set here.  Otherwise the cache lives at one fixed
path inside the checkout (``<repo>/.jax_cache``, gitignored): the
directory is part of what a later process must find again, so it is never
derived from a temp name, a process id or the time.
"""
from __future__ import annotations

import os

import jax

CACHE_DIR = os.path.normpath(os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "..", "..", "..",
    ".jax_cache"))


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    return CACHE_DIR
