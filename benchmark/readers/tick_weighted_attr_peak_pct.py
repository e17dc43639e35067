"""100 x (a counter of the program, averaged over the window's ticks
weighted by their length) / (a figure of the chip from ``peaks.json``):
e.g. bytes of recurrent state held by live sequences over the chip's HBM.
The average is ``readers/tick_weighted_attr_pct.py``'s (a tick's value is
the counter on the first span under it that owns one); only the
denominator differs: a peak of the device, not a total of the family's
shapes.  Whole window, host clock.  None when no tick carries the counter
(the parent of the PR that added it) or without peaks.
args: attr, peak (a key of the device's entry in ``peaks.json``)."""

from benchmark.readers import tick_weighted_attr_pct


def read(facts, args, ctx):
    if ctx.peaks is None or not ctx.peaks.get(args["peak"]):
        return None
    return tick_weighted_attr_pct.read(
        {**facts, "shapes": {"_peak": ctx.peaks[args["peak"]]}},
        {"attr": args["attr"], "total": "_peak"}, ctx)
