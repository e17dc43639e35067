"""The device as JAX reports it, the table of peaks, the compile cache and
the count of programs built.  A device that is not a TPU, or not in
``peaks.json``, is an error: nothing here falls back."""

from __future__ import annotations

import os
import re
from typing import Any, Dict, List

from benchmark.lib import spec

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class NoChip(SystemExit):
    """Raised before any result is printed; the process exits non-zero."""


def enable_compile_cache() -> str:
    """JAX's persistent compilation cache at a fixed path: where
    ``JAX_COMPILATION_CACHE_DIR`` is set JAX reads it itself and nothing
    is set in code, otherwise ``<checkout>/.jax_cache`` (the path is part
    of the cache key, so it never comes from a temporary name)."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    import jax

    path = os.path.join(spec.CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def claim_devices(chips: int, allow_cpu: bool = False) -> List[Any]:
    """The first ``chips`` devices; exits non-zero (no result line) when
    JAX finds no TPU or fewer chips than the cell asks for.  ``allow_cpu``
    exists for the CPU rehearsal in ``benchmark/tests`` only — the command
    line cannot set it."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" and not allow_cpu:
        raise NoChip(f"benchmark: no TPU — JAX found platform "
                     f"'{devices[0].platform}'; nothing is measured "
                     f"without the chip")
    if len(devices) < chips:
        raise NoChip(f"benchmark: the cell asks for {chips} chip(s), JAX "
                     f"found {len(devices)}")
    return list(devices[:chips])


def peaks_for(device_kind: str) -> Dict[str, float]:
    table = spec.load_json(os.path.join(spec.BENCH_DIR, "peaks.json"))
    if device_kind not in table or device_kind.startswith("_"):
        raise spec.SpecError(
            f"device kind {device_kind!r} is not in benchmark/peaks.json "
            f"({[k for k in table if not k.startswith('_')]}); add its "
            f"published peaks with their source — there is no default")
    return table[device_kind]


class CompileClock:
    """Executables JAX built (compiled, or loaded from the persistent
    cache) and the seconds that took, from jax.monitoring's own events."""

    def __init__(self):
        import jax.monitoring

        self.seconds = 0.0
        self.programs = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_kw) -> None:
        if event == _COMPILE_EVENT:
            self.seconds += duration
            self.programs += 1

    def mark(self) -> tuple:
        return (self.programs, self.seconds)

    def since(self, mark: tuple) -> Dict[str, float]:
        return {"programs": self.programs - mark[0],
                "seconds": self.seconds - mark[1]}


def memory_peak_bytes(devices) -> int:
    """``peak_bytes_in_use`` on the fullest chip (0 where the backend keeps
    no statistics, i.e. the CPU rehearsal)."""
    peak = 0
    for d in devices:
        stats = d.memory_stats()
        if stats:
            peak = max(peak, int(stats.get("peak_bytes_in_use",
                                           stats.get("bytes_in_use", 0))))
    return peak


def resident_bytes(devices) -> int:
    """``bytes_in_use`` on the fullest chip right now."""
    used = 0
    for d in devices:
        stats = d.memory_stats()
        if stats:
            used = max(used, int(stats.get("bytes_in_use", 0)))
    return used


def describe(devices) -> Dict[str, Any]:
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}


def mosaic_kernels(lowered_text: str) -> Dict[str, int]:
    """Mosaic kernels a lowered program calls, by kernel function name, with
    the number of call sites in the text (a jitted wrapper is one function
    called from every layer, so the count is not the depth).  Empty = the
    XLA composition: no ``tpu_custom_call`` in the program."""
    names = re.findall(r'@tpu_custom_call\(.*?kernel_name = "([^"]+)"',
                       lowered_text)
    return {n: names.count(n) for n in sorted(set(names))}
