"""PR 64: the set-up table of PERF.md section 5 from the logs a call of
``pr64_cells.sh`` left under ``chiprun_out/<tag>/``: a row a run
(``<cell>.change.cold.log`` / ``.warm.log``): the printed ``setup_s``, the
reader's own ``process start -> window``, the five ``setup_*`` metrics, and the
items of the reader's ``# set-up:`` commentary in seconds (``import`` among the
items: ``setup_import_s`` was a metric in this PR's first round, 0.11-0.12 s in
every run, and is a line of the commentary since).

    python3 tools/chip_calls/pr64_table.py chiprun_out/p64cA [chiprun_out/p64cB ...]
"""
import glob
import json
import os
import re
import sys

ITEMS = [("start", "process start -> setup/import"), ("import", "setup/import"),
         ("weights", "setup/import -> setup/engine_init"),
         ("init", "setup/engine_init"), ("batch", "setup/engine_init -> setup/init"),
         ("params", "setup/init_parameters"), ("check", "the engine -> the scheduler"),
         ("ladder", "the shape ladder"), ("preroll", "the last ladder tick"),
         ("rest", "the parameters -> the window")]
METRICS = ["setup_engine_init_s", "setup_trace_lower_s", "setup_cache_read_s",
           "setup_compile_s", "setup_programs_compiled"]


def row(path):
    text = open(path).read().splitlines()
    last = next((l for l in reversed(text) if l.startswith("{")), None)
    if last is None:
        return None
    m = {k: v["value"] for k, v in json.loads(last)["metrics"].items()}
    said = [l[2:] for l in text if l.startswith("# ")]
    printed = next((re.search(r"set-up ([0-9.]+) s;", l).group(1)
                    for l in said if re.search(r": set-up [0-9.]+ s;", l)), "?")
    own = next((re.search(r"instant ([0-9.]+) s by", l).group(1)
                for l in said if "by the program's own marks" in l), "?")
    items = {}
    for l in said:
        got = re.match(r"set-up:\s+([0-9.]+) s  (.*)", l)
        if got:
            for key, head in ITEMS:
                if got.group(2).startswith(head) and key not in items:
                    builds = re.search(r"\[(\d+) builds: trace \+ lowering "
                                       r"([0-9.]+), cache read ([0-9.]+), "
                                       r"compile ([0-9.]+)\]", got.group(2))
                    items[key] = got.group(1) + (
                        " ({} builds {:.1f})".format(
                            builds.group(1), sum(map(float, builds.groups()[1:])))
                        if builds else "")
    steps = next((l.split("did not hold: ")[1] for l in said
                  if "did not hold: " in l), "?")
    name = os.path.basename(os.path.dirname(path)) + " " \
        + os.path.basename(path).replace(".log", "")
    return "| " + " | ".join(
        [name, printed, own] + [f"{m[k]:.2f}" if k in m else "-" for k in METRICS]
        + [items.get(k, "-") for k, _ in ITEMS] + [steps]) + " |"


def main(dirs):
    head = ["run", "setup_s", "own"] + [k.replace("setup_", "") for k in METRICS] \
        + [k for k, _ in ITEMS] + ["step programs not from the cache"]
    print("| " + " | ".join(head) + " |")
    print("|" + " --- |" * len(head))
    for d in dirs:
        for path in sorted(glob.glob(os.path.join(d, "*.log"))):
            if ".t0." in path or "warming" in path:
                continue
            line = row(path)
            if line:
                print(line)


if __name__ == "__main__":
    main(sys.argv[1:])
