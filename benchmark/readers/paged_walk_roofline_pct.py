"""Share of its roofline the paged decode walk reaches: the least time the
chip could take for what the traced stretch's forwards asked of it (the
larger of FLOPs over the peak and bytes over the HBM peak,
``lib/costs_paged.py``: what the mathematics needs, not what the path
executes), summed over those forwards, over the kernel's device time in the
stretch.  No call is paired with a forward by order (a decode step is in
flight at both ends of the stretch): both sums are over the stretch, as
``mla_roofline_pct`` and ``gdn_roofline_pct`` do.

What was asked comes from the program's own counters, moved onto the
profiler's clock, on the spans that start inside the stretch: the table
blocks the one-token rows hold in BOTH kinds of program, ``read_blocks`` on
the ticks' ``decode`` spans (the consumed decode step's rows) and
``row_blocks`` on ``engine/build_batch`` (the one-token rows beside a
batch's chunks).

None when no call of the kernel is in the trace (the XLA composition, or a
program without the layer), when the program records no such counter, or
without peaks.  args: pattern."""

import re

from benchmark.lib import costs, costs_paged, tracing
from benchmark.readers import _host_labels, kernel_meta_ms

_COUNTERS = {"decode": "read_blocks", "engine/build_batch": "row_blocks"}


def asked(facts):
    """{counter: [values]} on the spans that start inside the stretch (on
    the profiler's clock)."""
    off = _host_labels.offset_ns(facts)
    out = {key: [] for key in _COUNTERS.values()}
    if off is None:
        return out
    lo, hi = facts["view"].window()
    for r in _host_labels.tracer_spans(facts):
        key, a = _COUNTERS.get(r["name"]), r.get("attrs") or {}
        if key in a and lo <= r["t0_ns"] + off <= hi:
            out[key].append(int(a[key]))
    return out


def read(facts, args, ctx):
    view, shapes = facts.get("view"), facts.get("shapes") or {}
    if view is None or ctx.peaks is None or "kv_heads" not in shapes:
        return None
    rx = re.compile(args["pattern"])
    calls = [(e.start, e.end) for e in view.device_events
             if rx.search(kernel_meta_ms.kernel_of(e.name) or "")]
    if not calls:
        return None
    bs = int(ctx.config["serve"]["block_size"])
    blocks = [n for v in asked(facts).values() for n in v if n > 0]
    took = tracing.total(tracing.union(calls)) / 1e9
    if not blocks or took <= 0:
        return None
    least = sum(costs.roofline(
        *costs_paged.decode_read_costs(shapes, n, bs), 1.0,
        ctx.peaks)["least_s"] for n in blocks)
    ctx.log(f"paged walk roofline: {len(blocks)} forwards of the stretch "
            f"held {sum(blocks)} table blocks, least "
            f"{1e3 * least / len(blocks):.3f} ms of "
            f"{1e3 * took / len(blocks):.3f} ms a forward in the kernel")
    return 100.0 * least / took
