"""JAX's persistent compilation cache, kept at one fixed place.

A cold run of the relational MLP step spends about half a minute
compiling, and a whole model step longer; the cache lets the next process
on the same machine skip that. Entry points call :func:`enable_compile_cache`
in ``main``, never at import time.
"""
from __future__ import annotations

import os
import pathlib

import jax

#: ``<repo root>/.jax_cache``; this file is ``<root>/src/repro/launch/``
DEFAULT_DIR = str(pathlib.Path(__file__).resolve().parents[3] / ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing else is set. Otherwise the cache goes to :data:`DEFAULT_DIR`,
    a fixed path: the path is part of each entry's key, so a directory
    that moves between runs never hits."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
