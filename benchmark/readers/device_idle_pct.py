"""1 - union of the device's operation intervals over the traced stretch,
averaged over the devices."""


def read(facts, args, ctx):
    view = facts.get("view")
    if view is None or not view.device_events:
        return None
    lo, hi = view.window()
    return 100.0 * (1.0 - view.busy_seconds() / ((hi - lo) / 1e9))
