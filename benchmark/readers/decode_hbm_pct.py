"""Share of the HBM bandwidth roofline the pure-decode ticks reach: bytes a
tick must read (every weight once + the keys and values of every live
context token, from shapes) over the device-busy time of those ticks.

A tick is found on the profiler's clock as a ``bench/tick`` span; it is a
pure-decode tick when the program's ``engine/decode_step`` annotation lies
inside it.  Live tokens at that instant come from the harness's own request
records, moved onto the profiler's clock."""

import bisect

from benchmark.lib import costs, tracing
from benchmark.readers import _host_labels


def read(facts, args, ctx):
    view, off = facts.get("view"), _host_labels.offset_ns(facts)
    if view is None or off is None or ctx.peaks is None or not view.devices:
        return None
    ticks = view.host_named(r"^bench/tick$")
    starts = [e.start for e in view.host_named(r"^engine/decode_step$")]
    busy = view.busy(view.devices[0])
    nbytes = secs = 0.0
    for t in ticks:
        i = bisect.bisect_left(starts, t.start)
        if i >= len(starts) or starts[i] >= t.end:
            continue
        mono = (t.start - off) / 1e9
        live = 0
        for plen, _olen, _sub, toks in facts["tracks"]:
            if toks and toks[0] <= mono and toks[-1] >= mono:
                live += plen + bisect.bisect_right(toks, mono)
        nbytes += costs.decode_tick_bytes(facts["shapes"],
                                          facts["weight_bytes"], live)
        secs += tracing.total(tracing.clip(busy, t.start, t.end)) / 1e9
    if secs <= 0:
        return None
    return 100.0 * nbytes / secs / ctx.peaks["hbm_bytes_per_s"]
