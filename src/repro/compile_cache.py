"""JAX's persistent compilation cache for the repo's entry points.

Entry points (``chip_smoke.py``, ``benchmarks/run.py``,
``benchmarks/bench_round.py``, ``examples/``) call
:func:`enable_compile_cache` once, before their first compile; importing
this module changes nothing. Where ``JAX_COMPILATION_CACHE_DIR`` is set,
JAX reads it itself and the helper sets no other directory. Otherwise the
cache lives at one fixed path inside the checkout, ``<repo>/.jax_cache``:
never a temporary name, so a second run in the same checkout finds what
the first one compiled.
"""
from __future__ import annotations

import os
from pathlib import Path

#: the cache directory when ``JAX_COMPILATION_CACHE_DIR`` is unset
DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
