"""Persistent XLA compilation cache placement for the repo's entry-point
scripts (``chip_smoke.py``, ``bench.py``, ``bench_serving.py``, the
``tools/`` scripts that run on a chip).

The directory is part of the cache key, so it must not move between runs:
where ``JAX_COMPILATION_CACHE_DIR`` is set JAX already reads it and nothing
is set in code; otherwise the cache lives at ``<checkout>/.jax_cache`` —
never a temporary name, a pid or a time.  The library itself never calls
this: a script's ``main`` does, before its first compilation.
"""

from __future__ import annotations

import os

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def enable_compile_cache() -> str:
    """Make JAX's persistent compilation cache active; returns the
    directory in use."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    import jax

    path = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
