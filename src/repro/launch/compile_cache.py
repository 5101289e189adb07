"""JAX's persistent compilation cache, for the entry points.

Every sweep point is its own compiled program (the cursor is static aux
data of ``SweepState``), so a cold production sweep compiles a few hundred
small programs. The entry points — ``chip_smoke.py``, ``benchmarks/run.py``,
``python -m repro.launch.serve_qr``, ``python -m repro.launch.train`` — call
:func:`enable_compile_cache` at start-up; the library never does.
"""
from __future__ import annotations

import os
import pathlib

import jax

# <repo>/.jax_cache (git-ignored): a fixed path, so a second run in the same
# checkout finds the first run's programs.
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and no
    other directory is set here; otherwise the cache goes to
    ``<repo>/.jax_cache``. Programs are cached however fast they compiled:
    most per-point sweep programs compile in under a second."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
