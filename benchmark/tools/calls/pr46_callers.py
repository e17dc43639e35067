"""PR 46: the new cell's own mix at another number of callers (and as many
state slots), one window each in a process of its own as the cell's runs
are: the reading of ``total_tok_s`` and ``tpot_p50_ms`` at 128 and 384
callers beside the cell's 256 (``PERF.md`` section 6, PR 46; the cell stays
at 256).  ``run_cell``'s ``overrides`` mark the line ``"overrides": true``:
it is no contract line.

    python3 benchmark/tools/calls/pr46_callers.py <callers> <seed>
"""

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))))))

from benchmark import run                               # noqa: E402

CELL = "serve-jamba2-reason-closed256"


def main(argv) -> int:
    callers, seed = int(argv[0]), int(argv[1])
    out = run.run_cell(CELL, seed, 51.0, False, overrides={
        "config": {"serve": {"max_ragged_sequence_count": callers}},
        "traffic": {"clients": callers}})
    facts = out.pop("_facts")
    print(json.dumps({"callers": callers, **out,
                      "preemptions": facts["preemptions"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
