"""Share of its roofline a latent-attention kernel reaches: the least time
the chip could take for what the traced stretch's forwards asked of it (the
larger of FLOPs over the peak and bytes over the HBM peak,
``lib/costs_mla.py``: what the mathematics needs, not what the path
executes), summed over those forwards, over the kernel's device time in the
stretch.  No call is paired with a forward by order (a decode step is in
flight at both ends of the stretch): both sums are over the stretch.

What was asked comes from the program's own counters, moved onto the
profiler's clock, on the spans that start inside the stretch.  ``which:
decode`` reads the table blocks the one-token rows hold in BOTH kinds of
program: ``read_blocks`` on the ticks' ``decode`` spans (the consumed decode
step's rows) and ``row_blocks`` on ``engine/build_batch`` (the one-token
rows beside a batch's chunks), against the kernel's whole time in the
stretch: a loop at its prefill capacity may run no pure-decode tick in a
stretch of seconds, and the walk runs in every mixed tick.  ``which:
prefill`` reads ``attn_pairs`` and ``which: expand`` reads ``ctx_rows`` on
``engine/build_batch``.

None when no call of the kernel is in the trace (the XLA composition, or a
program without the layer), when the program records no such counter (a
tree from before it), or without peaks.  args: pattern, which
(decode|prefill|expand)."""

import re

from benchmark.lib import costs, costs_mla, tracing
from benchmark.readers import _host_labels, kernel_meta_ms

_COUNTERS = {"decode": {"decode": "read_blocks",
                        "engine/build_batch": "row_blocks"},
             "prefill": {"engine/build_batch": "attn_pairs"},
             "expand": {"engine/build_batch": "ctx_rows"}}


def _asked(facts, which):
    """The counters' values on the spans that start inside the stretch (on
    the profiler's clock)."""
    off = _host_labels.offset_ns(facts)
    if off is None:
        return []
    lo, hi = facts["view"].window()
    keys = _COUNTERS[which]
    out = []
    for r in _host_labels.tracer_spans(facts):
        key, a = keys.get(r["name"]), r.get("attrs") or {}
        if key in a and lo <= r["t0_ns"] + off <= hi:
            out.append(int(a[key]))
    return out


def read(facts, args, ctx):
    view, shapes = facts.get("view"), facts.get("shapes") or {}
    if view is None or ctx.peaks is None or "kv_lora_rank" not in shapes:
        return None
    rx = re.compile(args["pattern"])
    calls = [(e.start, e.end) for e in view.device_events
             if rx.search(kernel_meta_ms.kernel_of(e.name) or "")]
    if not calls:
        return None
    which = args["which"]
    if which == "decode":
        bs = int(ctx.config["serve"]["block_size"])
        cost = lambda n: costs_mla.decode_read_costs(shapes, n, bs)
    elif which == "prefill":
        cost = lambda n: costs_mla.prefill_read_costs(shapes, n)
    else:
        cost = lambda n: costs_mla.expand_costs(shapes, n)
    asked = [n for n in _asked(facts, which) if n > 0]
    took = tracing.total(tracing.union(calls)) / 1e9
    if not asked or took <= 0:
        return None
    least = sum(costs.roofline(*cost(n), 1.0, ctx.peaks)["least_s"]
                for n in asked)
    ctx.log(f"mla {which} roofline: {len(asked)} forwards of the stretch, "
            f"least {1e3 * least / len(asked):.3f} ms of "
            f"{1e3 * took / len(asked):.3f} ms a forward in the kernel")
    return 100.0 * least / took
