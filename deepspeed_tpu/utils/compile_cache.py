"""Persistent XLA compilation cache placement for the repo's entry-point
scripts (``chip_smoke.py``, ``benchmark/run.py``, the ``tools/`` scripts
that run on a chip).

The directory is part of the cache key, so it must not move between runs:
where ``JAX_COMPILATION_CACHE_DIR`` is set JAX already reads it and nothing
is set in code; otherwise the cache lives at ``<checkout>/.jax_cache`` —
never a temporary name, a pid or a time.  The library itself never calls
this: a script's ``main`` does, before its first compilation.

What the cache keys on is the library's business, though.  By default the
key is computed from the module with its debug information stripped, so it
ignores ``jax.named_scope``, the ``op_name`` of every operation and source
locations: a program that differs from a cached one only in its scopes
loads the cached executable and shows the OLD names to a profiler (seen on
the chip, PERF.md section 6, PR 23).  The span and scope names are what the
engines publish to an operator and to the benchmark's readers, so both
engines call :func:`key_cache_on_names` when they are built: a change of
names then compiles once more instead of reporting another program's.
Where such a recompile shows: the build's ``setup/build_program`` record on
``observability.tracer.process_tracer()`` reads ``cache: miss`` under the
program's own name (and the benchmark's ``setup_programs_compiled`` counts a
step program in a warm run), so a ``setup_s`` that differs between a parent
and its change names the programs a moved line invalidated.
"""

from __future__ import annotations

import os

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def key_cache_on_names() -> None:
    """Keep scopes, ``op_name`` and source locations in the persistent
    cache's key (``jax_compilation_cache_include_metadata_in_key``).
    Costs one cold compile whenever a traced source line moves; changes
    nothing where no cache directory is set."""
    import jax

    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)


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
