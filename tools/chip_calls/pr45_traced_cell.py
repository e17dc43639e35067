"""PR 45: one traced run of a serving cell (``benchmark/run.py``'s own
``run_cell``: the contract line is printed as it prints it), and from the
same run's Tracer records what the benchmark has no reader for: the sums of
``latent_key_steps`` / ``latent_live_key_steps`` over the
``engine/build_batch`` spans of the measured window
(``pr44_traced_cell.py``'s way, for the latent engine's counters).

    python tools/chip_calls/pr45_traced_cell.py <cell> <seed>"""

import json
import os
import sys

sys.path.insert(0, os.getcwd())

from benchmark import run  # noqa: E402

KEYS = ("latent_key_steps", "latent_live_key_steps")


def main():
    cell, seed = sys.argv[1], int(sys.argv[2])
    out = run.run_cell(cell, seed, 51.0, True)
    facts = out.pop("_facts")
    print(json.dumps(out), flush=True)
    start, stop = facts["t_start_ns"], facts["t_stop_ns"]
    sums, batches = dict.fromkeys(KEYS, 0), 0
    for r in facts["tracer_records"]:
        at = r.get("attrs") or {}
        if r["name"] == "engine/build_batch" and KEYS[0] in at \
                and start <= r["t0_ns"] < stop:
            batches += 1
            for k in KEYS:
                sums[k] += int(at.get(k, 0))
    print("# latent key steps over the window's %d tiled batches: %s"
          % (batches, json.dumps(sums)), flush=True)


if __name__ == "__main__":
    main()
