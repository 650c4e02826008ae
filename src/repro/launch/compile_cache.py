"""JAX's persistent compilation cache for this repo's entry points.

Entry points (``chip_smoke.py``, ``examples/*``, ``launch/serve.py``)
call :func:`use_compile_cache` first thing in ``main``; importing
``repro`` never turns the cache on, so tests and described-topology
compiles stay off it.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
this sets no other directory.  Otherwise the cache lives at a fixed
``<checkout>/.jax_cache`` (listed in ``.gitignore``), so the next run
in the same checkout finds it; a per-run name never would.
"""

from __future__ import annotations

import os
import pathlib

import jax

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT = pathlib.Path(__file__).resolve().parents[3]
DEFAULT_DIR = CHECKOUT / ".jax_cache"


def use_compile_cache() -> str:
    """Turn the persistent cache on for this process; returns its
    directory.  Call before the first compile: JAX fixes the cache when
    it first compiles."""
    if not os.environ.get(CACHE_ENV):
        jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    # cache every program: the smoke phases' small compiles add up
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return jax.config.jax_compilation_cache_dir
