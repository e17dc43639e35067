"""Share of its roofline a gated-delta-rule kernel reaches: the least time
the chip could take for what the traced stretch's forwards asked of it (the
larger of FLOPs over the peak and bytes over the HBM peak,
``lib/costs_gdn.py``: the recurrence's own operations and bytes, not the
chunked form's), summed over those forwards, over the kernel's device time
in the stretch.

No kernel call is paired with a forward by counting: since the scheduler
dispatches a decode step ahead, a step is in flight at both ends of the
stretch.  Both sums are over the stretch instead.  What was asked comes from
the program's own counters on the spans that precede each dispatch, moved
onto the profiler's clock and kept when they start inside the stretch:
``which: step`` reads ``seqs`` on ``engine/decode_prep`` (live sequences of
a decode step; the kernel's time is kept inside the pure-decode ticks only,
as ``scope_ms`` finds them, so the single-token rows of mixed ticks, whose
count no counter gives, are in neither sum); ``which: chunk`` reads
``chunk_tokens`` and ``chunk_seqs`` on ``engine/build_batch``.  A forward
cut by an edge of the stretch is in one sum and only partly in the other:
one forward in the ~100 a stretch holds.

None when no call of the kernel is in the trace (the XLA composition, or a
program without the layer), when the program records no such counters, or
without peaks.  args: pattern, which (step|chunk)."""

import re

from benchmark.lib import costs, costs_gdn, tracing
from benchmark.readers import _host_labels, kernel_meta_ms, scope_ms


def _asked(facts, lo, hi, which):
    """[(counter values)] of the forwards dispatched inside [lo, hi] on the
    profiler's clock."""
    off = _host_labels.offset_ns(facts)
    if off is None:
        return []
    name, keys = ("engine/decode_prep", ("seqs",)) if which == "step" \
        else ("engine/build_batch", ("chunk_tokens", "chunk_seqs"))
    out = []
    for r in _host_labels.tracer_spans(facts):
        a = r.get("attrs") or {}
        if r["name"] == name and lo <= r["t0_ns"] + off <= hi \
                and all(k in a for k in keys):
            out.append(tuple(int(a[k]) for k in keys))
    return out


def read(facts, args, ctx):
    view, shapes = facts.get("view"), facts.get("shapes") or {}
    if view is None or ctx.peaks is None or "gdn_layers" not in shapes:
        return None
    rx = re.compile(args["pattern"])
    calls = [(e.start, e.end) for e in view.device_events
             if rx.search(kernel_meta_ms.kernel_of(e.name) or "")]
    if not calls:
        return None
    lo, hi = view.window()
    which = args["which"]
    spans = tracing.union(calls)
    if which == "step":
        inside = tracing.union(scope_ms._decode_ticks(view))
        if not inside:
            return None
        spans = tracing.subtract(spans, tracing.gaps(
            inside, spans[0][0], spans[-1][1]))
        # the decode steps whose dispatch fell into a pure-decode tick
        asked = [a for t0, t1 in inside
                 for a in _asked(facts, t0, t1, which)]
        cost = lambda a: costs_gdn.step_costs(shapes, a[0])
    else:
        asked = [a for a in _asked(facts, lo, hi, which) if a[0] > 0]
        cost = lambda a: costs_gdn.chunk_costs(shapes, *a)
    took = tracing.total(spans) / 1e9
    if not asked or took <= 0:
        return None
    least = sum(costs.roofline(*cost(a), 1.0, ctx.peaks)["least_s"]
                for a in asked)
    ctx.log(f"gdn {which} roofline: {len(asked)} forwards of the stretch, "
            f"least {1e3 * least / len(asked):.3f} ms of "
            f"{1e3 * took / len(asked):.3f} ms a forward in the kernel")
    return 100.0 * least / took
