"""Find the knee of an open-loop serving cell once, on the chip: the cell's
own mix (its arrival process, lengths, pre-roll and window) at several fixed
rates, each rate with several seeds, all in ONE process on one server (the
set-up is paid once; between windows the scheduler runs until idle).

    python3 benchmark/tools/knee_sweep.py --tag <name> --cell <cell> [--seconds S] [--seeds 11,12,13] <rate> [<rate> ...]

Rates are taken in ascending order and the sweep stops after the first rate
that is not sustained.  A rate is sustained when, pooled over its seeds,

* at least 95% of the requests due in the windows finished within the drain;
* no more requests are unfinished at the windows' ends than at their middles,
  beyond the noise of such a count (two standard deviations of a Poisson
  count: end <= mid + 2 sqrt(mid)): the queue is not growing.

The knee is the highest sustained rate; the cell's traffic file then gets
0.8 x knee as a number, and the table goes to PERF.md.
"""

from __future__ import annotations

import argparse
import copy
import json
import math
import os
import sys

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, _CHECKOUT)

from benchmark import run                               # noqa: E402
from benchmark.lib import stats                         # noqa: E402


def pooled(rows) -> dict:
    """One rate's windows as one line of the table."""
    due = sum(r["attempted"] for r in rows)
    mid = sum(r["unfinished_mid"] for r in rows)
    end = sum(r["unfinished_end"] for r in rows)
    out = {"rate_per_s": rows[0]["rate_per_s"], "windows": len(rows),
           "due": due, "failed": sum(r["failed"] for r in rows),
           "unfinished_mid": mid, "unfinished_end": end,
           "preemptions": sum(r["preemptions"] for r in rows)}
    for k in ("out_tok_s", "ttft_p50_ms", "ttft_p90_ms", "tpot_p50_ms",
              "tpot_p90_ms", "gen_late_p90_ms", "kv_live_pct"):
        out[k] = [r[k] for r in rows]
    out["sustained"] = bool(out["failed"] <= 0.05 * max(due, 1) and
                            end <= mid + 2.0 * math.sqrt(mid))
    return out


def main(argv=None, allow_cpu: bool = False, overrides=None) -> list:
    ap = argparse.ArgumentParser()
    ap.add_argument("rates", nargs="+", type=float)
    ap.add_argument("--cell", required=True)
    ap.add_argument("--tag", required=True)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--seeds", default="11,12,13")
    a = ap.parse_args(argv)
    seeds = [int(x) for x in a.seeds.split(",")]
    bench, ctx, _dev = run.make_context(a.cell, seeds[0], 0.0, False,
                                        overrides, allow_cpu)
    seconds = a.seconds if a.seconds is not None else bench["run_seconds"]
    out_dir = os.path.join(_CHECKOUT, "chiprun_out", a.tag)
    os.makedirs(out_dir, exist_ok=True)

    from benchmark.runners import serve_ragged
    server = serve_ragged.Server(ctx)
    table = []
    for rate in sorted(a.rates):
        rows = []
        for seed in seeds:
            mix = copy.deepcopy(ctx.traffic)
            mix["arrivals"]["rate_per_s"] = rate
            res = server.window(mix, seed, seconds)
            server.sched.run_until_idle()
            f = res["facts"]
            row = {"rate_per_s": rate, "seed": seed,
                   "attempted": res["attempted"], "failed": res["failed"],
                   "unfinished_mid": f["unfinished_mid"],
                   "unfinished_end": f["unfinished_end"],
                   "preemptions": f["preemptions"],
                   "kv_live_pct": f["kv_live_pct"],
                   "ttft_p90_ms": stats.pct(f["ttft_ms"], 90),
                   "tpot_p90_ms": stats.pct(f["tpot_ms"], 90),
                   "gen_late_p90_ms": stats.pct(f["gen_late_ms"], 90),
                   "programs_built_window": f["programs_built_window"],
                   "out_tok_s": f["out_tok_s"], **res["end_to_end"]}
            rows.append(row)
            print("# window " + json.dumps(row), flush=True)
        table.append(pooled(rows))
        print(json.dumps(table[-1]), flush=True)
        with open(os.path.join(out_dir, "sweep.json"), "w") as fh:
            json.dump({"cell": a.cell, "seconds": seconds, "seeds": seeds,
                       "rates": table}, fh, indent=1)
        if not table[-1]["sustained"]:
            break
    ok = [r["rate_per_s"] for r in table if r["sustained"]]
    print(f"knee (highest sustained rate swept): {max(ok) if ok else None}")
    return table


if __name__ == "__main__":
    main()
    sys.exit(0)
