"""Device milliseconds per tick of the traced stretch of the operations
whose ``op_name`` lies under a ``jax.named_scope`` of the program, over every
tick the stretch holds, mixed and pure-decode alike (``scope_ms`` divides by
a training stretch's steps or keeps the pure-decode ticks alone; a closed
loop at its prefill capacity may run none of those in a stretch of seconds,
and the scope's operations run in its mixed ticks too).  The events and
their ``op_name`` are ``scope_ms``'s.  None when no operation carries an
``op_name`` under the scope (a program without the scope), or the stretch
holds no tick.  args: scope (a regular expression searched in the
``op_name``)."""

import re

from benchmark.lib import tracing
from benchmark.readers import _units, scope_ms


def read(facts, args, ctx):
    view, n = facts.get("view"), _units.count(facts, "tick")
    if view is None or not n or not view.devices:
        return None
    rx = re.compile(args["scope"])
    per_dev = {}
    for dev, s, e, op, _label in scope_ms._events(facts):
        if rx.search(op):
            per_dev.setdefault(dev, []).append((s, e))
    if not per_dev:
        return None
    ns = sum(tracing.total(tracing.union(v)) for v in per_dev.values())
    ctx.log(f"device ms per tick under {args['scope']!r}: "
            f"{ns / len(view.devices) / n / 1e6:.3f} over {n} ticks")
    return ns / len(view.devices) / n / 1e6
