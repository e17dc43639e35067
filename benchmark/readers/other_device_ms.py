"""Device-busy milliseconds per tick or step that are neither the named
attention kernels nor collectives: MLP, projections, lm_head, optimizer and
whatever else XLA emits without a stable name.  args: exclude, per."""

from benchmark.lib import tracing
from benchmark.readers import _units


def read(facts, args, ctx):
    view, n = facts.get("view"), _units.count(facts, args["per"])
    if view is None or not n:
        return None
    busy = view.busy_seconds()
    named = view.seconds_matching(
        args["exclude"] + "|" + tracing._COLLECTIVE.pattern)
    return 1e3 * max(busy - named, 0.0) / n
