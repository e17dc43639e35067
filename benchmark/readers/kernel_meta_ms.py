"""Device milliseconds per step or tick of the Mosaic calls whose kernel
function's name matches a pattern.  The name is what the program passes as
``pl.pallas_call(metadata={"kernel": ...})``: it reaches the compiled custom
call as ``frontend_attributes={kernel_metadata={"kernel":"<name>"}}``, which
is part of the instruction text a TPU trace gives as the event's name (the
instruction's own name may be a flax scope or a jitted wrapper).  Logs the
names it found with their milliseconds, so a run says which kernel ran from
the trace and not only from the lowered text.  None when no call carries a
matching name (a program from before PR 23 prints ``kernel_metadata={}``).
args: pattern, per (tick|step)."""

import re

from benchmark.lib import tracing
from benchmark.readers import _units

_KERNEL = re.compile(r'kernel_metadata=\{\s*"kernel"\s*:\s*"([^"]+)"')


def kernel_of(event_text: str):
    """The kernel function's name in an event's instruction text, or None."""
    m = _KERNEL.search(event_text)
    return m.group(1) if m else None


def read(facts, args, ctx):
    view, n = facts.get("view"), _units.count(facts, args["per"])
    if view is None or not n or not view.devices:
        return None
    rx = re.compile(args["pattern"])
    per_dev = {d: [] for d in view.devices}
    by_name = {}
    for e in view.device_events:
        name = kernel_of(e.name)
        if name is None or not rx.search(name):
            continue
        per_dev[e.device].append((e.start, e.end))
        by_name[name] = by_name.get(name, 0) + e.dur
    if not by_name:
        return None
    nd = len(view.devices)
    ctx.log(f"kernels matching {args['pattern']!r} in the trace, ms per "
            f"{args['per']}: " + ", ".join(
                f"{k} {v / nd / n / 1e6:.3f}"
                for k, v in sorted(by_name.items())))
    return 1e3 * sum(tracing.total(tracing.union(v))
                     for v in per_dev.values()) / nd / n / 1e9
