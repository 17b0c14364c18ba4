"""JAX's persistent compilation cache for this repository's programs.

Called by the programs themselves (``chip_smoke.py``, ``examples/``,
``benchmarks/``), never on import of ``repro``: tests and library users
keep JAX's own defaults.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT = Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    no other directory is set here.  Otherwise the cache lives at
    ``<checkout>/.jax_cache``: a fixed path, so a later process of the
    same checkout finds what an earlier one compiled.  Every program is
    cached, however quickly it compiled.
    """
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(CHECKOUT / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
