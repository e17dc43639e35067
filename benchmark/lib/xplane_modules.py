"""The program executions of an ``.xplane.pb``.

Beside its "XLA Ops" line (one event an instruction: ``lib/tracing`` and
``lib/xplane_ops`` read that), every ``/device:TPU:<n>`` plane of a TPU
profile has an "XLA Modules" line with ONE event per execution of a compiled
program, named ``jit_<program>(<fingerprint>)`` where ``<program>`` is the
``__name__`` of the jitted function (seen on jax 0.9.0, TPU v5 lite, PR 33:
every operation of the five serving cells' stretches lies inside one such
event, and no two events overlap).  Times are ``ProfileData``'s, the clock
``lib/tracing.TraceView`` is on.
"""

from __future__ import annotations

import re
from typing import List, Tuple

MODULE_LINE = "XLA Modules"
_NAME = re.compile(r"^jit_(.+?)(\(\d+\))?$")


def program_of(event_name: str) -> str:
    """``decode_step`` of ``jit_decode_step(7246663385873248887)``; a name
    of another form is returned as it is."""
    m = _NAME.match(event_name)
    return m.group(1) if m else event_name


def device_modules(path: str) -> List[Tuple[int, int, int, str]]:
    """[(device index, start ns, end ns, program)] for every event of the
    "XLA Modules" line of every ``/device:TPU:<n>`` plane, by start."""
    import jax

    out = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        m = re.match(r"/device:TPU:(\d+)$", plane.name)
        if not m:
            continue
        for line in plane.lines:
            if line.name == MODULE_LINE:
                out += [(int(m.group(1)), int(ev.start_ns),
                         int(ev.start_ns + ev.duration_ns),
                         program_of(ev.name)) for ev in line.events]
    return sorted(out, key=lambda x: (x[1], x[0]))
