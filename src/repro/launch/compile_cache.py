"""JAX's persistent compilation cache at a fixed, placeable directory.

Entry points (``chip_smoke.py``, the benchmarks) call
:func:`use_compile_cache` once before their first compile; importing
``repro`` never does.  The directory is part of each entry's key, so it is
fixed: ``JAX_COMPILATION_CACHE_DIR`` when set (JAX reads that variable
itself and nothing is changed here), else ``<repo root>/.jax_cache``.
"""
from __future__ import annotations

import os
import pathlib

#: the default cache directory: ``.jax_cache`` at the repository root
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn on the persistent compile cache; returns its directory."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    import jax
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
