"""JAX's persistent compilation cache for the repo's entry points.

Scripts that drive the chip (``chip_smoke.py``, ``benchmarks/run.py``, the
``examples/``) call :func:`enable_compile_cache` once, before they compile
anything.  Library code never touches the cache.
"""

from __future__ import annotations

import os


def enable_compile_cache(checkout: str) -> str:
    """Use a persistent compile cache; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    nothing is set here.  Otherwise the cache lives at
    ``<checkout>/.jax_cache``: a fixed path, because the path is part of
    what a later run must look up to find the cached programs.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    path = os.path.join(os.path.abspath(checkout), ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
