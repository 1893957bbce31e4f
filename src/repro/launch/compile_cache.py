"""Where JAX keeps its persistent compilation cache.

A full-width meta step takes minutes to compile; the persistent cache lets
a second process with the same program skip that. The directory is placed
from outside the program: ``JAX_COMPILATION_CACHE_DIR``, when set, is read
by JAX itself and nothing here overrides it; otherwise the cache goes to a
fixed directory inside the checkout. The path is part of the cache key, so
it never carries a temp name, a PID or a timestamp.

Call ``enable_compile_cache`` from an entry point's ``main`` — never at
import time.
"""
from __future__ import annotations

import os

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
# <repo>/.jax_cache (this file is <repo>/src/repro/launch/compile_cache.py)
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))),
    ".jax_cache",
)


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
