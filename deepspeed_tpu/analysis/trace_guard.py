"""Runtime recompile/transfer guard ("dslint" pass 3).

Static lint can't see a shape that quietly varies step to step; this
guard proves at runtime that a warmed-up region is **steady-state**:

* **recompiles** — counted via ``jax.monitoring``'s backend-compile
  event (by the program's one listener, ``observability/tracer.py``:
  which program a region built is the ``setup/build_program`` record it
  left on ``process_tracer()``), so ANY new executable built inside the
  guarded region (a jit cache miss, a new eager-op shape) trips it;
* **explicit host syncs** — ``jax.device_get`` / ``jax.block_until_ready``
  calls are counted (patched for the guard's scope), catching the
  "fetch a flag every step" class on every backend;
* **implicit transfers** — ``jax.transfer_guard_*`` is armed at the
  chosen level. Note the CPU backend's device buffers ARE host memory,
  so device→host enforcement only has teeth on real accelerators; the
  recompile and sync counters carry the assertion on CPU tier-1 runs.

Usage::

    with TraceGuard(max_compiles=0, max_host_syncs=0) as tg:
        step()          # warmed-up steady-state work
    # raises TraceGuardError on violation; tg.compiles/tg.host_syncs

The pytest fixture lives in ``tests/conftest.py`` (``trace_guard``).
"""

from __future__ import annotations

import contextlib
from typing import Optional

__all__ = ["TraceGuard", "TraceGuardError", "compile_count"]


class TraceGuardError(AssertionError):
    """A guarded region recompiled or synced more than allowed."""


def _counts() -> tuple:
    """(backend compiles, jaxpr traces) so far, from the program's one
    ``jax.monitoring`` listener (``observability/tracer.py``: the same
    events it folds into ``setup/build_program`` records)."""
    from deepspeed_tpu.observability.tracer import build_totals

    totals = build_totals()
    return totals["programs"], totals["traces"]


def compile_count() -> int:
    """Process-wide backend compiles observed since ``deepspeed_tpu`` was
    imported (monotonic; snapshot-and-diff around regions)."""
    return _counts()[0]


class TraceGuard:
    """Context manager asserting a region is recompile/transfer-free.

    Parameters
    ----------
    max_compiles: backend compiles allowed inside the region (0 for a
        steady-state assertion). ``None`` disables the check (counting
        still happens).
    max_host_syncs: explicit ``jax.device_get``/``block_until_ready``
        calls allowed. ``None`` (default) disables the check — serving
        ticks legitimately fetch sampled tokens.
    d2h / h2d / d2d: transfer-guard levels ("allow", "log", "disallow",
        "log_explicit", "disallow_explicit") or None to leave the
        ambient setting. Default arms device→host at "disallow"
        (implicit transfers raise on backends where d2h is a real
        transfer).
    label: names the region in error messages.
    """

    def __init__(self, max_compiles: Optional[int] = 0,
                 max_host_syncs: Optional[int] = None,
                 d2h: Optional[str] = "disallow",
                 h2d: Optional[str] = None,
                 d2d: Optional[str] = None,
                 label: str = "guarded region"):
        self.max_compiles = max_compiles
        self.max_host_syncs = max_host_syncs
        self.d2h, self.h2d, self.d2d = d2h, h2d, d2d
        self.label = label
        self.compiles = 0
        self.retraces = 0
        self.host_syncs = 0
        self._stack: Optional[contextlib.ExitStack] = None
        self._c0 = 0
        self._t0 = 0
        self._orig_device_get = None
        self._orig_block = None

    # -- explicit-sync counting ---------------------------------------- #
    def _patch_syncs(self) -> None:
        import jax

        self._orig_device_get = jax.device_get
        self._orig_block = jax.block_until_ready
        guard = self

        def counted_device_get(x):
            guard.host_syncs += 1
            return guard._orig_device_get(x)

        def counted_block(x):
            guard.host_syncs += 1
            return guard._orig_block(x)

        jax.device_get = counted_device_get
        jax.block_until_ready = counted_block

    def _unpatch_syncs(self) -> None:
        import jax

        if self._orig_device_get is not None:
            jax.device_get = self._orig_device_get
        if self._orig_block is not None:
            jax.block_until_ready = self._orig_block

    def __enter__(self) -> "TraceGuard":
        import jax

        self._stack = contextlib.ExitStack()
        if self.d2h is not None:
            self._stack.enter_context(
                jax.transfer_guard_device_to_host(self.d2h))
        if self.h2d is not None:
            self._stack.enter_context(
                jax.transfer_guard_host_to_device(self.h2d))
        if self.d2d is not None:
            self._stack.enter_context(
                jax.transfer_guard_device_to_device(self.d2d))
        self._patch_syncs()
        self._c0, self._t0 = _counts()
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        self._unpatch_syncs()
        assert self._stack is not None
        self._stack.close()
        compiles, retraces = _counts()
        self.compiles, self.retraces = compiles - self._c0, retraces - self._t0
        if exc_type is not None:
            return False
        problems = []
        if self.max_compiles is not None and \
                self.compiles > self.max_compiles:
            problems.append(
                f"{self.compiles} backend compile(s) "
                f"(allowed {self.max_compiles}; {self.retraces} "
                "retrace(s)) — a steady-state region recompiled: check "
                "for shape drift, weak-typed python scalars, or new "
                "eager op shapes")
        if self.max_host_syncs is not None and \
                self.host_syncs > self.max_host_syncs:
            problems.append(
                f"{self.host_syncs} explicit host sync(s) "
                f"(device_get/block_until_ready; allowed "
                f"{self.max_host_syncs}) — the host blocked on the "
                "device inside the hot region")
        if problems:
            raise TraceGuardError(
                f"TraceGuard[{self.label}]: " + "; ".join(problems))
        return False
