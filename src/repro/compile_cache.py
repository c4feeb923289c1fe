"""JAX's persistent compilation cache for the repo's entry points.

``chip_smoke.py``, ``benchmarks/run.py`` and ``repro.launch.serve`` call
``enable_compile_cache()`` before they compile anything; tests do not.
Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
that directory stands. Otherwise the cache lives at ``.jax_cache/`` in
the repo root: a fixed path, because the path is part of what a later
run must find again.
"""
from __future__ import annotations

import os
from pathlib import Path

CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the cache on and cache every compile, however short; returns
    the directory in use."""
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return jax.config.jax_compilation_cache_dir
