"""JAX's persistent compilation cache for this checkout's entry points.

Compiling the serving and training programs for a TPU takes tens of
seconds; the persistent cache lets the next process reuse them.  Entry
points (`chip_smoke.py`, the examples, the benchmarks) call `enable` once
at start-up; importing this module changes nothing.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already keeps its cache
there and `enable` leaves it alone.  Otherwise the cache goes to
``.jax_cache/`` at the root of the checkout: a fixed path, because the
path is part of what a later process must match to find the entries.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable() -> str:
    """Turn the persistent cache on; returns the directory it uses."""
    if os.environ.get(ENV):
        return os.environ[ENV]
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
