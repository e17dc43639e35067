"""Device milliseconds per tick or step of the operations whose label
matches a pattern, from the device trace.  args: pattern, per (tick|step)."""

from benchmark.readers import _units


def read(facts, args, ctx):
    view, n = facts.get("view"), _units.count(facts, args["per"])
    if view is None or not n:
        return None
    return 1e3 * view.seconds_matching(args["pattern"]) / n
