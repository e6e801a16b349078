"""JAX's persistent compilation cache for the repo's entry points.

Entry points (``chip_smoke.py``, ``benchmarks/run.py``) call
``enable_compile_cache()`` once at start-up; importing the library turns
nothing on. Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it
itself and no other directory is set here. Otherwise the cache lives at
``<checkout>/.jax_cache`` (listed in ``.gitignore``). The path is part of
each entry's key, so it is fixed: never a temporary name, a process id
or the time.
"""
from __future__ import annotations

import os
import pathlib

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT = pathlib.Path(__file__).resolve().parents[2]


def compile_cache_dir() -> pathlib.Path:
    """Where the persistent compilation cache lives for this process."""
    env = os.environ.get(ENV_VAR)
    return pathlib.Path(env) if env else CHECKOUT / ".jax_cache"


def enable_compile_cache() -> pathlib.Path:
    """Turn the persistent compilation cache on; return its directory."""
    path = compile_cache_dir()
    if not os.environ.get(ENV_VAR):
        jax.config.update("jax_compilation_cache_dir", str(path))
    return path
