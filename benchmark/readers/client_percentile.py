"""A percentile of a per-request series the harness clocked itself
(``ttft_ms``, ``tpot_ms``, ``gen_late_ms``).  args: series, q."""

from benchmark.lib import stats


def read(facts, args, ctx):
    return stats.pct(facts.get(args["series"], ()), float(args["q"]))
