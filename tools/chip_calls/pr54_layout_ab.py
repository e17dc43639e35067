"""PR 54, step 1: the three training-attention layouts through the benchmark's own training program.

Written for the tree of commit 444c052 (PR 53), the last that has the switch: it runs from a checkout of that commit
(`--tree <dir>`; the tree this file is committed in has no such function any more).  Nothing under benchmark/ is
touched: `one` sets the process default a DeepSpeed config's `attention_layout` key would set
(`ops.attention.set_default_attention_layout`) and then calls `benchmark.run.run_cell`, the body of
`python3 benchmark/run.py`; the set-up clock starts where run.py's starts, at the import of `benchmark.run`.

    one    one run of one cell under one layout, in a process of its own; the last line of its output is one JSON object
    drive  a traced run a layout first (the lowered step's kernels prove which family ran; the per-layer metrics come
           from it), then ROUNDS untraced rounds, the layouts' order rotating a round; one process a run, no JAX here
    table  the medians, spreads and signs of a `runs.jsonl`, and step 2's rule on each family (no JAX)

    chiprun --timeout 3300 -- python3 tools/chip_calls/pr54_layout_ab.py drive --tag p54c1 \
        --workload train-gpt2large-d64-s1k --rounds 6 --seconds 30 --selftest
    chiprun --chips 4 --timeout 2400 -- python3 tools/chip_calls/pr54_layout_ab.py drive --tag p54c2 \
        --workload train-mistral7b-z3tp-s4k --rounds 6 --seconds 20 --short paired=2
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import subprocess
import sys
import time

LAYOUTS = ("bshd", "folded", "paired")
BOUND = 0.01            # train_tok_s_chip's bound in BENCHMARK.json
# a family's own kernels, as the lowered step names them (`paired` has none at 128-wide heads: it falls back to folded)
OWN = {"bshd": "_fwd_kernel", "folded": "_fwd_kernel_folded", "paired": "_fwd_kernel_paired"}


def one(args) -> int:
    tree = os.path.abspath(args.tree)
    sys.path.insert(0, tree)
    os.chdir(tree)
    from benchmark import run                           # the set-up clock starts here, as in run.py

    from deepspeed_tpu.ops import attention

    attention.set_default_attention_layout(args.layout)
    overrides = None
    if args.rehearse:                                   # the CPU rehearsal of benchmark/tests, at its tiny sizes
        from benchmark.tests.rehearsal_sizes import TINY
        overrides = TINY[args.workload]
    out = run.run_cell(args.workload, args.seed, args.seconds, bool(args.trace), overrides=overrides,
                       allow_cpu=args.rehearse)
    facts = out.pop("_facts")
    assert attention.get_default_attention_layout() == args.layout
    line = {"layout": args.layout, "workload": args.workload, "seed": args.seed, "seconds": args.seconds,
            "trace": args.trace, "correct": out["correct"], "attempted": out["attempted"], "failed": out["failed"],
            "metrics": {k: v["value"] for k, v in out["metrics"].items()},
            "tokens_per_s_chip": facts["tokens_per_s"] / facts["chips"], "steps": facts["steps"],
            "programs_built_window": facts["programs_built_window"],
            "attention_route": facts.get("attention_route"), "device": out["device"],
            "rehearsal": bool(out.get("rehearsal"))}
    print(json.dumps(line), flush=True)
    return 0


def _run_one(args, out_dir, layout, seed, seconds, trace, name):
    cmd = [sys.executable, os.path.abspath(__file__), "one", "--tree", args.tree, "--layout", layout,
           "--workload", args.workload, "--seed", str(seed), "--seconds", str(seconds), "--trace", str(trace)]
    if args.rehearse:
        cmd.append("--rehearse")
    t0 = time.monotonic()
    with open(os.path.join(out_dir, name + ".log"), "w") as log, \
            open(os.path.join(out_dir, name + ".err"), "w") as err:
        rc = subprocess.run(cmd, stdout=log, stderr=err, timeout=args.run_timeout).returncode
    wall = time.monotonic() - t0
    last = ""
    for last in open(os.path.join(out_dir, name + ".log")):
        pass
    try:
        line = json.loads(last)
    except ValueError:
        line = {"layout": layout, "error": last[:300]}
    line.update({"rc": rc, "wall_s": round(wall, 1), "run": name})
    with open(os.path.join(out_dir, "runs.jsonl"), "a") as f:
        f.write(json.dumps(line) + "\n")
    m = line.get("metrics", {})
    print(f"{name}: rc {rc} {wall:.0f} s correct={line.get('correct')} tok/s/chip="
          f"{m.get('train_tok_s_chip', line.get('tokens_per_s_chip'))} setup_s={m.get('setup_s')} "
          f"route={line.get('attention_route')}", flush=True)
    return line


def drive(args) -> int:
    out_dir = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
                           "chiprun_out", args.tag)
    os.makedirs(out_dir, exist_ok=True)
    short = dict((k, int(v)) for k, v in (s.split("=") for s in args.short))
    seed = args.seed0
    # -- first: which kernels each layout's step program calls, and the per-layer metrics ---------------------- #
    for layout in LAYOUTS:
        line = _run_one(args, out_dir, layout, seed, args.trace_seconds, 1, f"traced_{layout}")
        seed += 1
        route = line.get("attention_route") or []
        if not args.rehearse and not any(k.startswith(OWN[layout]) and
                                         (layout != "bshd" or "folded" not in k and "paired" not in k)
                                         for k in route):
            print(f"{layout}: NOT its own kernels in the lowered step: {route}", flush=True)
    if args.selftest:           # the families' self-test cases once, so that the A/B is of kernels that match
        with open(os.path.join(out_dir, "selftest.json"), "w") as log, \
                open(os.path.join(out_dir, "selftest.err"), "w") as err:
            rc = subprocess.run([sys.executable, "tools/kernel_selftest.py"], cwd=args.tree, stdout=log, stderr=err,
                                timeout=1200).returncode
        print(f"kernel_selftest: rc {rc}", flush=True)
    # -- the rounds: every layout once a round, the order rotating --------------------------------------------- #
    for r in range(args.rounds):
        order = LAYOUTS[r % 3:] + LAYOUTS[:r % 3]
        for layout in order:
            if r >= short.get(layout, args.rounds):
                continue
            _run_one(args, out_dir, layout, seed, args.seconds, 0, f"round{r + 1}_{layout}")
            seed += 1
    summary = table(os.path.join(out_dir, "runs.jsonl"))
    with open(os.path.join(out_dir, "table.json"), "w") as f:
        json.dump(summary, f, indent=1)
    print(json.dumps(summary, indent=1))
    return 0


def table(path):
    runs = [json.loads(l) for l in open(path)]
    rounds, traced = {}, {}
    for r in runs:
        if r.get("trace"):
            traced[r["layout"]] = r
        elif "metrics" in r:
            rounds.setdefault(r["run"].split("_")[0], {})[r["layout"]] = r
    out = {"workload": runs[0].get("workload"), "layouts": {}}
    for layout in LAYOUTS:
        mine = [rd[layout] for rd in rounds.values() if layout in rd]
        tok = [r["metrics"]["train_tok_s_chip"] for r in mine]
        setup = [r["metrics"]["setup_s"] for r in mine]
        t = traced.get(layout, {})
        row = {"rounds": len(tok), "train_tok_s_chip": tok,
               "median": statistics.median(tok) if tok else None,
               "spread": (max(tok) - min(tok)) / statistics.median(tok) if tok else None,
               "setup_s": setup, "setup_s_warm_median": statistics.median(setup[1:]) if len(setup) > 1 else None,
               "all_correct": all(r["correct"] and not r["failed"] and r["rc"] == 0 for r in mine),
               "attention_route": t.get("attention_route"),
               "traced": {k: t.get("metrics", {}).get(k) for k in (
                   "attn_kernel_ms_step", "attn_proj_ms_step", "flash_fwd_ms_step", "flash_bwd_dq_ms_step",
                   "flash_bwd_dkv_ms_step", "other_device_ms_step", "train_hbm_peak_gb")}}
        out["layouts"][layout] = row
    base = out["layouts"]["bshd"]
    for layout in LAYOUTS[1:]:
        row = out["layouts"][layout]
        # a round's sign: this layout against bshd in the same round
        ratios = [rd[layout]["metrics"]["train_tok_s_chip"] / rd["bshd"]["metrics"]["train_tok_s_chip"]
                  for rd in rounds.values() if layout in rd and "bshd" in rd]
        row["ratio_to_bshd_by_round"] = ratios
        if row["median"] and base["median"]:
            row["median_ratio_to_bshd"] = row["median"] / base["median"]
            # step 2's rule: the median beats bshd's by more than the bound AND every round agrees in sign
            row["wins"] = bool(row["median_ratio_to_bshd"] > 1 + BOUND and all(x > 1 for x in ratios))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    sub = ap.add_subparsers(dest="mode", required=True)
    here = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    for name in ("one", "drive"):
        p = sub.add_parser(name)
        p.add_argument("--tree", default=here)
        p.add_argument("--workload", required=True)
        p.add_argument("--seconds", type=float, required=True)
        p.add_argument("--rehearse", action="store_true")
        if name == "one":
            p.add_argument("--layout", choices=LAYOUTS, required=True)
            p.add_argument("--seed", type=int, required=True)
            p.add_argument("--trace", type=int, choices=(0, 1), required=True)
        else:
            p.add_argument("--tag", required=True)
            p.add_argument("--rounds", type=int, default=6)
            p.add_argument("--seed0", type=int, default=5400000010)
            p.add_argument("--trace-seconds", type=float, default=5.0)
            p.add_argument("--run-timeout", type=float, default=600.0)
            p.add_argument("--selftest", action="store_true")
            p.add_argument("--short", nargs="*", default=[], metavar="LAYOUT=ROUNDS")
    sub.add_parser("table").add_argument("runs")
    args = ap.parse_args(argv)
    if args.mode == "table":
        print(json.dumps(table(args.runs), indent=1))
        return 0
    return {"one": one, "drive": drive}[args.mode](args)


if __name__ == "__main__":
    sys.exit(main())
