"""Share of its roofline a step of learned sparse attention reaches over the
traced stretch: the least time the chip could take for what the stretch's
forwards asked of it (the larger of FLOPs over the peak and bytes over the
HBM peak, ``lib/costs_dsa.py``: what the mathematics needs), summed over
those forwards, over the device time of whatever implements the step.  That
time is found by kernel name (``args.pattern``, a Mosaic kernel) OR by scope
(``args.scope``, searched in the operations' ``op_name``), so the share reads
the same work whichever implements it.  Both sums are over the stretch (a
decode step is in flight at both of its ends).

What was asked comes from the program's own counters, moved onto the
profiler's clock, on the spans that start inside the stretch: ``idx_keys`` /
``sel_keys`` on the ticks' ``decode`` spans (the consumed decode step's rows)
and on ``engine/build_batch`` (the one-token rows beside a batch's chunks),
``idx_pairs`` / ``sel_pairs`` on ``engine/build_batch`` (the chunks' rows).
``which: index`` reads the ``idx_*`` pair, ``which: read`` the ``sel_*`` pair.

None when nothing in the trace matches the pattern or scope, when the
program records no such counter (a tree from before it), or without peaks.
args: which (index|read), and pattern or scope."""

import re

from benchmark.lib import costs, costs_dsa, tracing
from benchmark.readers import _host_labels, kernel_meta_ms, scope_ms

_COUNTERS = {"index": ("idx_keys", "idx_pairs"),
             "read": ("sel_keys", "sel_pairs")}
_SPANS = ("decode", "engine/build_batch")


def asked(facts, names):
    """[(keys, pairs)] of the spans that start inside the stretch (on the
    profiler's clock) and carry one of the two counters."""
    off = _host_labels.offset_ns(facts)
    if off is None:
        return []
    lo, hi = facts["view"].window()
    out = []
    for r in _host_labels.tracer_spans(facts):
        a = r.get("attrs") or {}
        if r["name"] in _SPANS and names[0] in a \
                and lo <= r["t0_ns"] + off <= hi:
            out.append((int(a[names[0]]), int(a.get(names[1], 0))))
    return out


def _device_ns(facts, args) -> int:
    view = facts["view"]
    if "pattern" in args:
        rx = re.compile(args["pattern"])
        calls = [(e.start, e.end) for e in view.device_events
                 if rx.search(kernel_meta_ms.kernel_of(e.name) or "")]
        return tracing.total(tracing.union(calls))
    rx = re.compile(args["scope"])
    per_dev = {}
    for dev, s, e, op, _label in scope_ms._events(facts):
        if rx.search(op):
            per_dev.setdefault(dev, []).append((s, e))
    return sum(tracing.total(tracing.union(v)) for v in per_dev.values())


def read(facts, args, ctx):
    view, shapes = facts.get("view"), facts.get("shapes") or {}
    if view is None or ctx.peaks is None or "index_head_dim" not in shapes:
        return None
    which = args["which"]
    cost = costs_dsa.index_costs if which == "index" else costs_dsa.read_costs
    forwards = [f for f in asked(facts, _COUNTERS[which]) if sum(f) > 0]
    took = _device_ns(facts, args) / 1e9
    if not forwards or took <= 0:
        return None
    least = sum(costs.roofline(*cost(shapes, *f), 1.0, ctx.peaks)["least_s"]
                for f in forwards)
    ctx.log(f"dsa {which} roofline: {len(forwards)} forwards of the "
            f"stretch, least {1e3 * least / len(forwards):.3f} ms of "
            f"{1e3 * took / len(forwards):.3f} ms a forward")
    return 100.0 * least / took
