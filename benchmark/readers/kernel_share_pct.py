"""Device time of the operations matching ``pattern`` as a share of those
matching ``of``.  args: pattern, of."""


def read(facts, args, ctx):
    view = facts.get("view")
    if view is None:
        return None
    whole = view.seconds_matching(args["of"])
    if whole <= 0:
        return None
    return 100.0 * view.seconds_matching(args["pattern"]) / whole
