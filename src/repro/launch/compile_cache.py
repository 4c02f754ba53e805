"""Persistent XLA compilation cache for the entry points.

A 32-layer train step takes tens of seconds to compile on a TPU, and every
fresh process pays it again unless the executable is cached on disk.  The
train and serve launchers and ``chip_smoke.py`` call
:func:`enable_compile_cache` before their first compile:

* if ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
  sets no other directory;
* otherwise the cache lives at ``<checkout>/.jax_cache`` — one fixed path,
  since the path is part of what makes a later process find an entry
  (``.gitignore`` lists it).
"""
from __future__ import annotations

import os
import pathlib

import jax

__all__ = ["enable_compile_cache", "DEFAULT_CACHE_DIR"]

DEFAULT_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its one directory and
    return that directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)
