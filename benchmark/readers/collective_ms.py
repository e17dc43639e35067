"""Device milliseconds per step of collective operations, all or only the
part during which no other operation ran on that device.  args: exposed."""

from benchmark.readers import _units


def read(facts, args, ctx):
    view, n = facts.get("view"), _units.count(facts, "step")
    if view is None or not n or len(view.devices) < 2:
        return None
    every, exposed = view.collective_seconds()
    return 1e3 * (exposed if args.get("exposed") else every) / n
