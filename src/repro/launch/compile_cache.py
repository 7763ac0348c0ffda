"""Placement of JAX's persistent compilation cache for the entry points.

Called from an entry point's ``main()`` before the first compile — never at
import and never from the tests.  Where ``JAX_COMPILATION_CACHE_DIR`` is
set, JAX reads it itself and nothing is set here.  Otherwise the cache goes
to a fixed directory inside the checkout: the directory is part of the
cache key, so it must not move from one run to the next.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: ``<repo>/.jax_cache`` (listed in .gitignore)
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def setup_compile_cache() -> str:
    """Return the directory JAX caches compiled programs in."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
