"""A count the runner made itself (e.g. programs built in the window).
args: key."""


def read(facts, args, ctx):
    return facts.get(args["key"])
