"""JAX's persistent compilation cache for the entry points.

Called from an entry point's ``main()`` (``chip_smoke.py``,
``benchmarks/run.py``, ``repro.launch.train``), never at import.  Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing else is
set; otherwise the cache lives at a fixed ``<checkout>/.jax_cache`` (the path
is part of the cache key, so it must not move between runs).
"""
from __future__ import annotations

import os
import pathlib

import jax

CHECKOUT = pathlib.Path(__file__).resolve().parents[2]
ENV_VAR = "JAX_COMPILATION_CACHE_DIR"


def enable() -> str:
    """Turn the cache on and return its directory."""
    path = os.environ.get(ENV_VAR)
    if not path:
        path = str(CHECKOUT / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path
