"""Share of its roofline the grouped-GEMM kernel reaches: the least time
the chip could take for the routed rows of each traced forward (the larger
of FLOPs over the peak and bytes over the HBM peak, ``lib/costs_moe.py``;
required operations, not executed ones), summed over the forwards, over the
device time of that kernel in the same forwards.

A forward's routed rows come from the program's own counters: ``tokens`` on
``engine/build_batch`` (a ragged batch) or ``seqs`` on ``engine/decode_prep``
(a decode step), times the experts a token is routed to.  Forwards and
kernel calls are paired by ORDER, not by clocks: every forward of the
program calls the kernel ``calls_per_layer x layers`` times and the device
runs them in the order they were dispatched, the traced stretch ends with
the window, and the device is idle when it starts (a tick fetches its
result before it returns); so the k-th group of calls from the end belongs
to the k-th forward before the window's end (one chip: the program that
has the kernel serves TP = 1).  Forwards with fewer than
``costs_moe.MIN_ROUTED_ROWS`` routed rows are left out of both sums and
counted in the log: below it ``min(experts, rows)`` over-counts the experts
whose weights must be read, and a share above 100% would be the count's
fault.  None when no call of the kernel is in the trace (a program without
the kernel, or without its ``kernel_metadata`` name), when the program
records no ``seqs`` / ``tokens`` counters, or when the calls do not divide
into whole forwards.  args: pattern[, calls_per_layer]."""

import re

from benchmark.lib import costs, costs_moe
from benchmark.readers import _host_labels, kernel_meta_ms

_COUNTER = {"engine/build_batch": "tokens", "engine/decode_prep": "seqs"}


def forwards(facts):
    """[(t0_ns, real tokens)] of every forward the window dispatched, by
    the counter span that precedes its dispatch, oldest first."""
    stop = facts.get("t_stop_ns")
    out = []
    for r in _host_labels.tracer_spans(facts):
        key = _COUNTER.get(r["name"])
        n = (r.get("attrs") or {}).get(key) if key else None
        if n is not None and (stop is None or r["t0_ns"] < stop):
            out.append((r["t0_ns"], int(n)))
    return sorted(out)


def read(facts, args, ctx):
    view, shapes = facts.get("view"), facts.get("shapes") or {}
    if view is None or ctx.peaks is None or "experts" not in shapes:
        return None
    rx = re.compile(args["pattern"])
    calls = sorted((e.start, e.dur) for e in view.device_events
                   if rx.search(kernel_meta_ms.kernel_of(e.name) or ""))
    per_fwd = int(args.get("calls_per_layer", 3)) * int(shapes["layers"])
    fwds = forwards(facts)
    if not calls or not fwds:
        return None
    if len(calls) % per_fwd or len(calls) // per_fwd > len(fwds):
        ctx.log(f"gmm roofline: {len(calls)} kernel calls in the trace do "
                f"not divide into forwards of {per_fwd} (or exceed the "
                f"{len(fwds)} forwards the program counted): not reported")
        return None
    groups = [calls[i:i + per_fwd] for i in range(0, len(calls), per_fwd)]
    least = took = 0.0
    left_out = by_bound = 0
    for (_t0, tokens), group in zip(fwds[-len(groups):], groups):
        rows = costs_moe.routed_rows(shapes, tokens)
        if rows < costs_moe.MIN_ROUTED_ROWS:
            left_out += 1
            continue
        secs = sum(d for _s, d in group) / 1e9
        r = costs.roofline(*costs_moe.grouped_ffn_costs(shapes, rows), secs,
                           ctx.peaks)
        least += r["least_s"]
        took += secs
        by_bound += r["bound"] == "memory"
    kept = len(groups) - left_out
    if took <= 0:
        return None
    ctx.log(f"gmm roofline: {kept} forwards of the stretch ({by_bound} "
            f"memory-bound, {kept - by_bound} compute-bound; {left_out} "
            f"left out with under {costs_moe.MIN_ROUTED_ROWS} routed rows), "
            f"least {1e3 * least / kept:.3f} ms of "
            f"{1e3 * took / kept:.3f} ms a forward in the kernel")
    return 100.0 * least / took
