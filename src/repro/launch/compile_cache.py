"""Where JAX's persistent compilation cache lives.

A cold full-width serving run spends most of its set-up compiling.  The
persistent cache keeps compiled programs on disk, so a later process on
the same installation loads them instead.  Placement is decided from
outside: ``JAX_COMPILATION_CACHE_DIR``, when set, is used as it is (JAX
reads it itself); otherwise the cache is the fixed directory
``<repo>/.jax_cache`` (listed in ``.gitignore``), never a temporary or
per-process path that a later run could not find.

Entry points turn the cache on; library code and tests never do.
"""
from __future__ import annotations

import os
import pathlib

import jax

DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def compile_cache_dir() -> str:
    """The directory the cache uses: the environment's, else the default."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(DEFAULT_DIR)


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory.

    Every program is cached, however quickly it compiled: set-up compiles
    dozens of sub-second programs (one weight-init program per leaf kind
    and shape), which JAX's default one-second floor would leave out."""
    path = compile_cache_dir()
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
