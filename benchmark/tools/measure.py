"""Measure cells the way the driver does: sets of runs of one cell, each run
a new process with another ``--seed``, then per metric the median of each set
and the spread (distance between the quartiles over the median) — and, since
quartiles over six runs cannot see one run in six, every run's values and
the full range (lowest and highest over the median).

    python3 benchmark/tools/measure.py --tag <name> [--sets 2]
        [--runs 6] [--seed0 100] [--trace 0|1] <cell> [<cell> ...]

Run it from the checkout to be measured (an unpacked ``git archive`` has its
own copy).  This parent never imports JAX (one process holds the chip: the
child).  Every child's output goes to
``chiprun_out/<tag>/<cell>.s<set>r<run>.log``, the run's side file (every
tick or step group) beside it, the summary to
``chiprun_out/<tag>/summary.json`` and to standard output.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import shutil
import subprocess
import sys
import time

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, _CHECKOUT)

from benchmark.lib import stats     # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("cells", nargs="+")
    ap.add_argument("--tag", required=True)
    ap.add_argument("--sets", type=int, default=2)
    ap.add_argument("--runs", type=int, default=6)
    ap.add_argument("--seed0", type=int, default=100)
    ap.add_argument("--trace", type=int, default=0)
    a = ap.parse_args()
    with open(os.path.join(_CHECKOUT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    seconds = bench["run_seconds"]
    out_dir = os.path.join(_CHECKOUT, "chiprun_out", a.tag)
    os.makedirs(out_dir, exist_ok=True)
    summary = {"seconds": seconds, "cells": {}}
    for cell in a.cells:
        sets = []
        for s in range(a.sets):
            rows = []
            for r in range(a.runs):
                seed = a.seed0 + 100 * s + r
                cmd = bench["command"] + [
                    "--workload", cell, "--seed", str(seed), "--seconds",
                    str(seconds), "--trace", str(a.trace)]
                t0 = time.time()
                p = subprocess.run(cmd, cwd=_CHECKOUT, capture_output=True,
                                   text=True)
                wall = time.time() - t0
                log = os.path.join(out_dir, f"{cell}.s{s}r{r}.log")
                with open(log, "w") as f:
                    f.write(p.stdout)
                    f.write("\n---- stderr (tail) ----\n")
                    f.write(p.stderr[-6000:])
                for side in glob.glob(os.path.join(
                        _CHECKOUT, "bench_out", cell, f"*_seed{seed}.json")):
                    shutil.copy(side, os.path.join(
                        out_dir, f"{cell}.s{s}r{r}." + os.path.basename(side)))
                row = {"seed": seed, "rc": p.returncode, "wall_s": wall}
                lines = [x for x in p.stdout.strip().splitlines() if x]
                if p.returncode == 0 and lines:
                    try:
                        row["result"] = json.loads(lines[-1])
                    except ValueError:
                        row["rc"] = -1
                rows.append(row)
                res = row.get("result", {})
                print(f"{cell} set {s} run {r} seed {seed}: rc {row['rc']} "
                      f"wall {wall:.0f} s correct {res.get('correct')} "
                      f"failed {res.get('failed')}/{res.get('attempted')} " +
                      " ".join(f"{k}={v['value']:.6g}" for k, v in
                               res.get("metrics", {}).items()), flush=True)
            sets.append(rows)
        summary["cells"][cell] = {"sets": sets, "stats": _stats(sets)}
        for name, st in summary["cells"][cell]["stats"].items():
            print(f"  {cell} {name}: medians {st['medians']} spreads "
                  f"{st['spreads']} widest {st['widest_spread']} range over "
                  f"all runs {st['range']}", flush=True)
        with open(os.path.join(out_dir, "summary.json"), "w") as f:
            json.dump(summary, f, indent=1)
    return 0


def _stats(sets):
    names = []
    for rows in sets:
        for row in rows:
            for k in row.get("result", {}).get("metrics", {}):
                if k not in names:
                    names.append(k)
    out = {}
    for name in names:
        med, spr, every = [], [], []
        for rows in sets:
            vals = [row["result"]["metrics"][name]["value"] for row in rows
                    if name in row.get("result", {}).get("metrics", {})]
            if name == "setup_s":       # each side's first run compiles
                vals = vals[1:] if len(vals) > 1 else vals
            med.append(stats.pct(vals, 50))
            spr.append(stats.spread(vals))
            every += vals
        widest = max([s for s in spr if s is not None], default=None)
        mid = stats.pct(every, 50)
        rng = [min(every) / mid - 1.0, max(every) / mid - 1.0] \
            if every and mid else None
        out[name] = {"medians": med, "spreads": spr, "widest_spread": widest,
                     "range": rng}
    return out


if __name__ == "__main__":
    sys.exit(main())
