"""Where the entry points keep JAX's persistent compile cache.

The cache is keyed on its directory among other things, so the
directory must not move between runs: a fixed ``.jax_cache/`` at the
repository root, never a temporary, per-process or dated name.
"""
from __future__ import annotations

import os

import jax

CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def use_compile_cache() -> str:
    """Point JAX's compile cache at :data:`CACHE_DIR`, unless
    ``JAX_COMPILATION_CACHE_DIR`` is set: JAX then already keeps it
    there, and this sets nothing.  Call before the first compile.
    Returns the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    return CACHE_DIR
