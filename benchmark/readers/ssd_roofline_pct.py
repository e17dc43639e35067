"""Share of its roofline a Mamba-2 recurrence kernel reaches: the least time the
chip could take for what a step program's launch asked of it (the larger of
FLOPs over the bf16 peak and bytes over the HBM peak, ``lib/costs_ssd.py``:
the recurrence's own operations and bytes), summed over the WHOLE executions
of the traced stretch, over the kernel's device time inside those same
executions.

Executions are joined to launches as ``ssm_roofline_pct`` joins them
(``readers/_launches.py``: by program name and order, anchored in time; the
stretch's first and last execution left out), so both sums are over the
same forwards.  What a launch asked for is the program's own counter
on the span that closes before its dispatch opens: ``which: step`` reads
``seqs`` on ``engine/decode_prep`` for executions of ``decode_step`` (the
one-token rows of mixed ticks, whose count no counter gives, are in neither
sum); ``which: chunk`` reads ``chunk_tokens`` and ``chunk_seqs`` on
``engine/build_batch`` for executions of ``ragged_step_*``.

None when no call of the kernel is in the trace (the XLA composition, or a
program without the layer: the parent of the PR that added it), without a
launch record or those counters, or without peaks.
args: pattern, which (step|chunk)."""

import bisect
import re

from benchmark.lib import costs, costs_ssd
from benchmark.readers import _launches, kernel_meta_ms
from benchmark.readers.ssm_roofline_pct import _asked_before


def read(facts, args, ctx):
    view, shapes = facts.get("view"), facts.get("shapes") or {}
    if view is None or ctx.peaks is None or "ssd_layers" not in shapes:
        return None
    rx = re.compile(args["pattern"])
    calls = sorted((e.start, e.end) for e in view.device_events
                   if rx.search(kernel_meta_ms.kernel_of(e.name) or ""))
    execs, _info = _launches.joined(facts)
    which = args["which"]
    closed, counters = _asked_before(facts, which)
    if not calls or not execs or not closed:
        return None
    program = "decode_step" if which == "step" else "ragged_step"
    cost = costs_ssd.step_costs if which == "step" else costs_ssd.chunk_costs
    starts = [c[0] for c in calls]
    least = took = 0.0
    n = 0
    for x in execs:
        row = x["launch"]
        if x["cut"] or row is None or not x["program"].startswith(program):
            continue
        i = bisect.bisect_right(closed, row["d0"]) - 1
        if i < 0 or counters[i][0] <= 0:
            continue
        inside = sum(e - s for s, e in calls[
            bisect.bisect_left(starts, x["start"]):
            bisect.bisect_right(starts, x["end"])] if e <= x["end"])
        if inside <= 0:
            continue
        least += costs.roofline(*cost(shapes, *counters[i]), 1.0,
                                ctx.peaks)["least_s"]
        took += inside / 1e9
        n += 1
    if not n:
        return None
    ctx.log(f"ssd {which} roofline: {n} whole executions of {program}*, "
            f"least {1e3 * least / n:.3f} ms of {1e3 * took / n:.3f} ms an "
            f"execution in the kernel")
    return 100.0 * least / took
