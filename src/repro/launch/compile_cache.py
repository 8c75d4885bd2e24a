"""Persistent XLA compilation cache for the entry points.

``enable()`` is called first by ``chip_smoke.py``, ``repro.launch.train``
and ``benchmarks/run.py``, before anything compiles. Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing is
set here. Otherwise the cache goes to ``.jax_cache/`` at the root of the
checkout: a fixed path, because the path is part of the cache key, so a
directory that moves never hits. ``.gitignore`` lists it.
"""
from __future__ import annotations

import os
import pathlib

import jax

CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable() -> str:
    """Turn the persistent cache on; returns the directory it uses."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
