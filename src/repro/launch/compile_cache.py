"""JAX's persistent compilation cache, placed from outside or at a fixed
path in the checkout.

``JAX_COMPILATION_CACHE_DIR``, when set, is used as it is (jax reads it
itself) and no other directory is set here.  Otherwise the cache lives
at ``<checkout>/.jax_cache``: a path that never depends on a temporary
name, a process id or the time, so a later run of the same program finds
what an earlier one compiled.
"""
from __future__ import annotations

import os

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = os.path.abspath(os.path.join(
    os.path.dirname(__file__), "..", "..", "..", ".jax_cache"))


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory it uses."""
    path = os.environ.get(ENV) or DEFAULT_DIR
    jax.config.update("jax_compilation_cache_dir", path)
    return path
