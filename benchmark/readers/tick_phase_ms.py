"""Mean host milliseconds per scheduler tick spent in the named tick phases
(the program's Tracer spans under each ``tick`` span), over the ticks of the
window.  args: phases."""

from benchmark.readers import _host_labels


def read(facts, args, ctx):
    ticks = _host_labels.window_ticks(facts)
    if not ticks:
        return None
    ns = sum(t["phases"].get(p, 0) for t in ticks for p in args["phases"])
    return ns / len(ticks) / 1e6
