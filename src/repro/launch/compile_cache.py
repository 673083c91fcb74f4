"""JAX's persistent compilation cache for the entry points.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing
else is set here.  Otherwise the cache lives at one fixed directory inside
the checkout, ``<repo>/.jax_cache`` (listed in ``.gitignore``): the path is
part of what the cache is found by, so it is never derived from a temporary
name, a process id or the time.  Call :func:`enable_compile_cache` before the
first computation (the backend reads the setting when it compiles).
"""
from __future__ import annotations

import os

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory it writes to."""
    path = os.environ.get(ENV_VAR)
    if not path:
        path = DEFAULT_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    return path
