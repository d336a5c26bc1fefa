"""JAX's persistent compilation cache, placed for this checkout."""
from __future__ import annotations

import os

import jax

# <checkout>/.jax_cache: a fixed path (git-ignored), so a later run of the
# same checkout finds what an earlier one compiled
CHECKOUT_CACHE_DIR = os.path.normpath(os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "..", "..", "..",
    ".jax_cache"))


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and no
    other directory is set here; otherwise the cache is
    :data:`CHECKOUT_CACHE_DIR`.  Called from entry points (``chip_smoke.py``,
    ``repro.launch.serve``, ``benchmarks.run``), never at import."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = CHECKOUT_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    # cache every executable, not only those that took a second to compile
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path
