"""Placement of JAX's persistent compilation cache for entry points.

A later run finds a cached program only when it looks in the same
directory, so the path is fixed: either the one ``JAX_COMPILATION_CACHE_DIR``
names (JAX reads that variable itself) or one inside the checkout.
Entry points call :func:`enable_compile_cache` first thing; importing the
library never touches the cache.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: ``<checkout>/.jax_cache`` (listed in ``.gitignore``).
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already uses it and
    no directory is set here.  Otherwise the cache goes to
    :data:`CHECKOUT_CACHE_DIR`.  Either way every compile is written, not
    only those over JAX's default one second: a run on a fresh machine
    starts with nothing compiled, and the engine's sub-second programs
    (admission, release) are compiled again by every run otherwise."""
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
