"""100 x sum(counter ``num``) / sum(counter ``den``) over the spans that
descend from the window's ticks of one ``kind``; each counter is read on
the span that owns it (e.g. ``tokens`` / ``bucket`` of the
``engine/build_batch`` spans under mixed ticks: useful tokens per padded
token of the ragged batches).  args: num, den, kind."""

from benchmark.readers import _tick_tree


def read(facts, args, ctx):
    _ticks, under = _tick_tree.descendants(facts, args["kind"])
    attrs = [r.get("attrs") or {} for r in under]
    den = sum(a.get(args["den"], 0) for a in attrs)
    if not den:
        return None
    return 100.0 * sum(a.get(args["num"], 0) for a in attrs) / den
