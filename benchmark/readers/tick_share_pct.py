"""Share of the window's scheduler ticks that had the named phase (a
``prefill`` phase = a mixed tick).  args: phase."""

from benchmark.readers import _host_labels


def read(facts, args, ctx):
    ticks = _host_labels.window_ticks(facts)
    if not ticks:
        return None
    return 100.0 * sum(1 for t in ticks if args["phase"] in t["phases"]) \
        / len(ticks)
