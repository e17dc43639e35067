"""One-off of PR 26: the device operations of a traced training run, by kind.

    python3 tools/chip_calls/pr26_trace_ops.py <checkout> <cell> <steps> <out.json>

Reads the newest xplane file under ``<checkout>/bench_out/<cell>/trace`` with the
benchmark's own ``TraceView`` and writes, per step and averaged over the devices:
the milliseconds of each collective kind (union per device, asynchronous start to
done), of the ``fusion`` operations that call an ``all-reduce-scatter`` computation
(how the TPU compiler writes a reduce-scatter: ``collective_ms_step`` does not count
them, on either side), and the 40 operations with most device time.
"""

import glob
import json
import os
import re
import sys


def main() -> None:
    checkout, cell, steps, out = sys.argv[1:5]
    sys.path.insert(0, checkout)
    from benchmark.lib.tracing import TraceView

    found = glob.glob(os.path.join(checkout, "bench_out", cell, "trace",
                                   "plugins", "profile", "*", "*.xplane.pb"))
    view = TraceView.from_xplane(max(found, key=os.path.getmtime))
    n = float(steps)
    kinds = {k: view.seconds_matching(k) * 1e3 / n for k in (
        "all-gather", "all-reduce", "all-to-all", "collective-permute",
        "reduce-scatter")}
    nd = max(len(view.devices), 1)
    scatter = sum(e.dur for e in view.device_events
                  if re.search(r"calls=%?all-reduce-scatter", e.name))
    all_s, exposed_s = view.collective_seconds()
    result = {
        "steps": n, "devices": nd,
        "busy_ms_step": view.busy_seconds() * 1e3 / n,
        "collective_ms_step": all_s * 1e3 / n,
        "collective_exposed_ms_step": exposed_s * 1e3 / n,
        "ms_step_by_kind": kinds,
        "reduce_scatter_fusion_ms_step": scatter / nd / 1e6 / n,
        "top_ops_ms_step": [[k, s * 1e3 / n] for k, s in view.top_ops(40)],
    }
    with open(out, "w") as f:
        json.dump(result, f, indent=1)
    print(json.dumps({k: v for k, v in result.items()
                      if k != "top_ops_ms_step"}))


if __name__ == "__main__":
    main()
