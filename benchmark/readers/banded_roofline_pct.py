"""Share of its roofline a paged attention kernel reaches on a model with
window and global layers: the least time the chip could take for what the
traced stretch's forwards asked of it (the larger of FLOPs over the peak and
bytes over the HBM peak, ``lib/costs_window.py``: what the mathematics
needs, not what the path executes), summed over those forwards, over the
kernel's device time in the stretch.  Both sums are over the stretch, as
``paged_walk_roofline_pct`` does.

What was asked comes from the program's own counters, moved onto the
profiler's clock, on the ``engine/build_batch`` and ``engine/decode_prep``
spans that start inside the stretch.  ``which: walk`` (the one-token rows,
``_decode_kernel``): ``read_blocks`` (table blocks the rows hold: every
global layer reads them) and ``read_blocks_win`` (in-band blocks, summed
over the window layers).  ``which: prefill`` (the chunks, the tiled
kernel): ``attn_pairs`` (causal pairs, a global layer) and
``attn_pairs_win`` (banded pairs, a window layer).

None when no call of the kernel is in the trace, when the program records no
such counter (a program without ``kv_groups``), or without peaks.
args: pattern, which."""

import re

from benchmark.lib import costs, costs_window, tracing
from benchmark.readers import _host_labels, kernel_meta_ms

_SPANS = ("engine/build_batch", "engine/decode_prep")
_COUNTERS = {"walk": ("read_blocks", "read_blocks_win"),
             "prefill": ("attn_pairs", "attn_pairs_win")}


def asked(facts, which):
    """[(first counter, second counter)] of the dispatches that start
    inside the stretch (on the profiler's clock) and carry both."""
    off = _host_labels.offset_ns(facts)
    if off is None:
        return []
    lo, hi = facts["view"].window()
    a, b = _COUNTERS[which]
    out = []
    for r in _host_labels.tracer_spans(facts):
        at = r.get("attrs") or {}
        if r["name"] in _SPANS and a in at and b in at \
                and lo <= r["t0_ns"] + off <= hi:
            out.append((int(at[a]), int(at[b])))
    return out


def read(facts, args, ctx):
    view, shapes = facts.get("view"), facts.get("shapes") or {}
    if view is None or ctx.peaks is None or "window_layers" not in shapes:
        return None
    rx = re.compile(args["pattern"])
    calls = [(e.start, e.end) for e in view.device_events
             if rx.search(kernel_meta_ms.kernel_of(e.name) or "")]
    which = args["which"]
    work = [w for w in asked(facts, which) if any(w)]
    took = tracing.total(tracing.union(calls)) / 1e9
    if not calls or not work or took <= 0:
        return None
    bs = int(ctx.config["serve"]["block_size"])
    if which == "walk":
        each = [costs_window.walk_costs(shapes, a, b, bs) for a, b in work]
    else:
        each = [costs_window.chunk_costs(shapes, a, b) for a, b in work]
    least = sum(costs.roofline(f, n, 1.0, ctx.peaks)["least_s"]
                for f, n in each)
    ctx.log(f"banded {which} roofline: {len(work)} forwards of the stretch "
            f"asked {sum(a for a, _ in work)} / {sum(b for _, b in work)} "
            f"({' / '.join(_COUNTERS[which])}), least "
            f"{1e3 * least / len(work):.3f} ms of "
            f"{1e3 * took / len(work):.3f} ms a forward in the kernel")
    return 100.0 * least / took
