"""JAX's persistent compilation cache for the repository's entry points.

A compile of a whole round program at a chip-sized shape takes about a
minute; the persistent cache lets every later process that compiles the same
program load it instead.  The cache's directory is part of what a hit needs,
so it is a fixed path: ``JAX_COMPILATION_CACHE_DIR`` where that is set (JAX
reads the variable itself), else ``<root>/.jax_cache`` inside the checkout.
"""
from __future__ import annotations

import os
import pathlib

import jax


def enable_compile_cache(root) -> str:
    """Point JAX's persistent compilation cache at its fixed directory and
    return that directory.  Call before the first compile."""
    configured = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if configured:
        return configured
    path = str(pathlib.Path(root).resolve() / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
