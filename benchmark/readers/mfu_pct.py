"""Model FLOP/s utilization of the window: required forward + backward
FLOPs per token (``lib/costs.py``) x tokens/s over chips x peak."""

from benchmark.lib import costs


def read(facts, args, ctx):
    if ctx.peaks is None or "tokens_per_s" not in facts:
        return None
    return costs.mfu_pct(facts["shapes"], facts["seq"],
                         facts["tokens_per_s"], facts["chips"], ctx.peaks)
