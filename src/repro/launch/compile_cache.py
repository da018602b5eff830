"""JAX's persistent compilation cache, kept at one fixed place.

A cold run of the relational MLP step spends about half a minute
compiling, and a whole model step longer; the cache lets the next process
on the same machine skip that. Entry points call :func:`enable_compile_cache`
in ``main``, never at import time.

The program's device ops carry named scopes (``ir.*``, ``rel.*``, ``lm.*``,
``train.*``) in their metadata, which a profiler capture shows. JAX keys a
cache entry with the metadata stripped by default, so a cache shared with
another version of the program could serve an executable of the same
computation without those scopes. :func:`key_on_metadata` keys with the
metadata; a source line that moves in a traced file then misses the cache
once.
"""
from __future__ import annotations

import os
import pathlib

import jax

#: ``<repo root>/.jax_cache``; this file is ``<root>/src/repro/launch/``
DEFAULT_DIR = str(pathlib.Path(__file__).resolve().parents[3] / ".jax_cache")


def key_on_metadata() -> None:
    """Key the persistent cache with each op's metadata, source paths cut
    to ``repro/...`` and ``benchmarks/...`` so that a checkout moved
    elsewhere still hits."""
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    jax.config.update("jax_hlo_source_file_canonicalization_regex",
                      r".*/(?=(?:repro|benchmarks)/)")


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on, keyed with op metadata
    (:func:`key_on_metadata`), and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    the directory is left to it. Otherwise the cache goes to :data:`DEFAULT_DIR`,
    a fixed path: the path is part of each entry's key, so a directory
    that moves between runs never hits."""
    key_on_metadata()
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
