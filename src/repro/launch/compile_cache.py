"""JAX's persistent compilation cache, one setting for every entry point.

The serve and train launchers, ``benchmarks.run`` and ``chip_smoke.py``
call :func:`enable_compile_cache` before they compile anything, so a later
process on the same machine reuses what an earlier one compiled.
"""

from __future__ import annotations

import os

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
# <checkout>/src/repro/launch/compile_cache.py -> <checkout>
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory.

    When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and no
    other directory is set here.  Otherwise the cache lives at the fixed
    ``<checkout>/.jax_cache``: the directory is part of the cache key, so
    a name that changed per run (a temp dir, a pid, a time) would never
    hit."""
    if not os.environ.get(ENV_VAR):
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(_CHECKOUT, ".jax_cache"))
    return jax.config.jax_compilation_cache_dir
