"""Share of the roofline the training attention kernels reach: the FLOPs
and bytes attention needs per step from shapes (``lib/costs.py``; the
chip's share on a mesh) over the kernels' device time.  Prints which bound
applies.  args: pattern."""

from benchmark.lib import costs
from benchmark.readers import _units


def read(facts, args, ctx):
    view, n = facts.get("view"), _units.count(facts, "step")
    if view is None or not n or ctx.peaks is None:
        return None
    secs = view.seconds_matching(args["pattern"]) / n
    if secs <= 0:
        return None
    flops, nbytes = costs.train_attention_step_costs(
        facts["shapes"], facts["batch"], facts["seq"])
    r = costs.roofline(flops / facts["chips"], nbytes / facts["chips"], secs,
                       ctx.peaks)
    ctx.log(f"attention roofline: {r['bound']}-bound, least "
            f"{r['least_s'] * 1e3:.2f} ms of {secs * 1e3:.2f} ms per step")
    return r["pct"]
