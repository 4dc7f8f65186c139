"""The persistent compilation cache that every entry point shares.

JAX reads ``JAX_COMPILATION_CACHE_DIR`` itself, and where it is set no
other directory is configured.  Where it is unset, entry points keep
their compiled programs in one fixed directory of the checkout,
``<checkout>/.jax_cache``: the directory is part of what a later run
looks up, so it must not depend on a temporary name, a process or the
time.
"""
from __future__ import annotations

import os

import jax

CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))
DEFAULT_DIR = os.path.join(CHECKOUT, ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent cache on for this process; returns its
    directory.  Call it from an entry point, before the first compile."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = DEFAULT_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    return path
