"""JAX's persistent compilation cache, placed for an entry point.

Entry points (``chip_smoke.py``, ``benchmarks/run.py``, the examples)
call ``enable_compile_cache()`` once from ``main``; importing the library
never touches it. ``JAX_COMPILATION_CACHE_DIR``, when set, names the
directory and JAX reads it itself. Otherwise the cache sits at a fixed
``<checkout>/.jax_cache`` (gitignored): the path is part of what a later
process must find again, so it is never built from a temp name, a pid or
the time.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT = Path(__file__).resolve().parents[3]
DEFAULT_DIR = CHECKOUT / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on and return its directory.

    Small programs are cached too: the minimum compile time and the
    minimum entry size are lowered to nothing, so a kernel that compiles
    in milliseconds is found again like a whole round scan."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path
