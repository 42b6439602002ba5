"""Where jax keeps its persistent compile cache.

``JAX_COMPILATION_CACHE_DIR``, when set, is read by jax itself and
nothing overrides it.  Otherwise entry points call
:func:`use_compile_cache` with the repository root, and the cache lives
at the fixed ``<root>/.jax_cache`` (listed in ``.gitignore``), so a
second run on the same checkout finds what the first compiled.
"""
from __future__ import annotations

import os

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"


def use_compile_cache(root: str) -> str:
    """Point jax's compile cache at ``$JAX_COMPILATION_CACHE_DIR`` or
    ``<root>/.jax_cache``; returns the directory in use."""
    if os.environ.get(ENV):
        return os.environ[ENV]
    path = os.path.join(os.path.abspath(root), ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
