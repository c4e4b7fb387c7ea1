"""JAX's persistent compilation cache at one fixed path.

Every restore or resize builds a fresh ``jax.jit`` of the step
(``core/elastic.py``), so without a persistent cache each one recompiles.
Call ``enable_compile_cache()`` from an entry point, before the first
compile; never at import time.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

# <checkout>/.jax_cache — fixed, so a later run finds what this one wrote
CACHE_DIR = str(Path(__file__).resolve().parents[3] / ".jax_cache")


def enable_compile_cache() -> str:
    """Turn on the persistent cache; returns the directory it uses.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX has already read it and
    nothing is set here; otherwise the cache lives at ``CACHE_DIR``.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    return CACHE_DIR
