"""Where JAX's persistent compilation cache lives.

A full-width serving program takes tens of seconds to compile, and a
fresh process compiles every one of them again unless the compiled
executables persist. The cache path is part of every entry's key, so
it must be the same from one run to the next: the helper below never
builds it from a temporary name, a pid or the time.
"""
from __future__ import annotations

import os
import pathlib

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
# <checkout>/.jax_cache (this file is <checkout>/src/repro/launch/...),
# listed in .gitignore
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; call before the first
    compile. Where JAX_COMPILATION_CACHE_DIR is set, JAX already keeps
    its cache there and nothing else is set; otherwise the cache goes
    to the fixed DEFAULT_DIR. Returns the directory in use."""
    placed = os.environ.get(ENV_VAR)
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
