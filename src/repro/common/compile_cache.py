"""The one place that configures JAX's persistent compilation cache.

A cache hits only when its directory stays put, because the path is part
of what JAX keys an entry on. So the directory is
``JAX_COMPILATION_CACHE_DIR`` where that is set, and otherwise the fixed
``.jax_cache/`` at the checkout root (listed in ``.gitignore``). Every
process of a multi-process run resolves the same directory, so one rank
compiles and the others load.
"""
from __future__ import annotations

import os

CHECKOUT_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))


def compile_cache_dir() -> str:
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or \
        os.path.join(CHECKOUT_ROOT, ".jax_cache")


def configure_compile_cache() -> str:
    """Point this process's persistent cache at `compile_cache_dir()` and
    cache every compile, however short. Returns the directory."""
    import jax

    path = compile_cache_dir()
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path
