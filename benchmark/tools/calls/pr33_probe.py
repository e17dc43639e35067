"""PR 33: one traced run of a cell in this process, then what the launch
readers read, written to ``chiprun_out/<dir>/<cell>.s<seed>.json.gz`` so
that they can be run again on the CPU: the Tracer's records of the last
seconds, the first device's "XLA Modules" events and busy intervals, the
clock anchor, and every garbage collection of the process that took over 5
ms (are the 100-170 ms host pauses every run logs under "the longest"
collections?).  (Call 1's form, through ``pr33_call01_first.sh``, also wrote
every plane's and line's name with its first events, which is how the
"XLA Modules" line was found, and the operations with the ``jit(...)`` of
their ``op_name``, which the first reader cut executions from.)

    python3 benchmark/tools/calls/pr33_probe.py <cell> <seed> <dir>
    python3 benchmark/tools/calls/pr33_probe.py --reread <dump.json.gz>

The second form needs no chip: it runs the two readers over a dump and
prints what they log and return.
"""
import gc
import gzip
import json
import os
import sys
import time

sys.path.insert(0, os.getcwd())
from benchmark import run                                   # noqa: E402
from benchmark.lib import xplane_modules                    # noqa: E402
from benchmark.readers import _host_labels, _launches      # noqa: E402


#: (start on time.monotonic_ns, ns, generation, objects collected)
_COLLECTIONS = []
_began = [0]


def _on_gc(phase, info):
    if phase == "start":
        _began[0] = time.monotonic_ns()
    elif time.monotonic_ns() - _began[0] > 5_000_000:
        _COLLECTIONS.append((_began[0], time.monotonic_ns() - _began[0],
                             info["generation"], info["collected"]))


def main():
    cell, seed, out_dir = sys.argv[1], int(sys.argv[2]), sys.argv[3]
    gc.callbacks.append(_on_gc)
    out = run.run_cell(cell, seed, 51.0, True)
    facts = out.pop("_facts")
    print(json.dumps(out), flush=True)
    path = (facts.get("capture") or {}).get("xplane")
    start, stop = facts["t_start_ns"], facts["t_stop_ns"]
    inside = [c for c in _COLLECTIONS if start <= c[0] < stop]
    print(f"# {len(inside)} collections over 5 ms in the window "
          f"(generation, ms, s into the window): " + ", ".join(
              f"{g} {ns / 1e6:.0f} {(t0 - start) / 1e9:.1f}"
              for t0, ns, g, _n in inside[:24]), flush=True)
    mods = xplane_modules.device_modules(path) if path else []
    _execs, info = _launches.joined(facts)
    dump = {"t_start_ns": start, "t_stop_ns": stop, "gc": _COLLECTIONS,
            "offset_ns": _host_labels.offset_ns(facts),
            "records": [r for r in facts["tracer_records"]
                        if r.get("t1_ns", r["t0_ns"]) >= stop - 8_000_000_000],
            "modules": mods,
            "busy": facts["view"].busy(min(m[0] for m in mods))
            if mods else [],
            "join": info and {k: v for k, v in info.items()
                              if k != "longest"}}
    os.makedirs(out_dir, exist_ok=True)
    with gzip.open(f"{out_dir}/{cell}.s{seed}.json.gz", "wt") as f:
        json.dump(dump, f)


def reread(path):
    from benchmark.lib.tracing import HostEvent, TraceView
    from benchmark.readers import (launch_device_ms_tick,
                                   launch_starved_ms_tick)

    class Ctx:
        peaks = None
        log = staticmethod(lambda msg: print("#", msg))

    with gzip.open(path, "rt") as f:
        d = json.load(f)
    # an anchor the dump's offset puts where the run's own was
    facts = {"tracer_records": d["records"], "t_start_ns": d["t_start_ns"],
             "t_stop_ns": d["t_stop_ns"], "capture": {"mono_sync_ns": 0},
             "view": TraceView([], [HostEvent("main", "bench/clock_sync",
                                              d["offset_ns"], 1)]),
             "_launch_executions": _launches.executions_of(
                 [tuple(m) for m in d["modules"]],
                 [tuple(b) for b in d["busy"]])}
    for kind in ("mixed+prefill", "decode"):
        print(kind, "starved (the dump's last seconds only)",
              launch_starved_ms_tick.read(facts, {"kind": kind}, Ctx()))
        for what in ("busy", "idle"):
            print(kind, what, launch_device_ms_tick.read(
                facts, {"kind": kind, "what": what}, Ctx()))


if __name__ == "__main__":
    reread(sys.argv[2]) if sys.argv[1] == "--reread" else main()
