"""benchmark/run.py — one run of one cell.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A new process per run.  It refuses to go on without a TPU (or with fewer
chips than the cell asks for), keeps JAX's persistent compilation cache at
``JAX_COMPILATION_CACHE_DIR`` or ``<checkout>/.jax_cache``, hands the cell to
the runner its configuration names, and prints as the last line of its
standard output one JSON object: ``correct``, ``attempted``, ``failed``,
``metrics``, ``device`` (and ``breakdown`` with ``--trace 1``).  With
``--trace 0`` the metrics are the cell's end-to-end metrics and the profiler
is never started; with ``--trace 1`` they are its per-layer metrics, each
produced by the reader its ``layer_metrics/<name>.json`` names.  Every line
before the last is commentary (prefixed ``# ``).
"""

from __future__ import annotations

import time

_T_PROCESS_START = time.monotonic()

import argparse          # noqa: E402
import dataclasses       # noqa: E402
import json              # noqa: E402
import os                # noqa: E402
import sys               # noqa: E402
from typing import Any, Callable, Dict, List, Optional   # noqa: E402

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _CHECKOUT not in sys.path:
    sys.path.insert(0, _CHECKOUT)

from benchmark.lib import device, spec      # noqa: E402


@dataclasses.dataclass
class RunContext:
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    seed: int
    seconds: float
    trace: bool
    devices: List[Any]
    peaks: Optional[Dict[str, float]]
    clock: device.CompileClock
    out_dir: str
    log: Callable[[str], None]


def _log(msg: str) -> None:
    print("# " + msg, flush=True)


def make_context(workload: str, seed: int, seconds: float, trace: bool,
                 overrides: Optional[Dict[str, Any]] = None,
                 allow_cpu: bool = False):
    """The cell's files read, its chips claimed, the compile cache placed:
    what a runner is handed.  Returns (BENCHMARK.json, context, device)."""
    bench = spec.benchmark_spec()
    cell = spec.cell(bench, workload)
    config = spec.config_for(bench, cell)
    mix = spec.traffic_for(cell)
    if overrides:
        _merge(config, overrides.get("config", {}))
        _merge(mix, overrides.get("traffic", {}))

    devices = device.claim_devices(int(cell["chips"]), allow_cpu=allow_cpu)
    dev = device.describe(devices)
    peaks = None if dev["platform"] != "tpu" else device.peaks_for(dev["kind"])
    cache_dir = device.enable_compile_cache()
    clock = device.CompileClock()
    out_dir = os.path.join(spec.CHECKOUT, "bench_out", workload)
    os.makedirs(out_dir, exist_ok=True)
    _log(f"{workload}: {dev['count']} x {dev['kind']} ({dev['platform']}), "
         f"seed {seed}, {seconds} s, trace {int(trace)}, compile cache "
         f"{cache_dir}")
    return bench, RunContext(config, mix, seed, seconds, trace, devices,
                             peaks, clock, out_dir, _log), dev


def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             overrides: Optional[Dict[str, Any]] = None,
             allow_cpu: bool = False) -> Dict[str, Any]:
    """Everything but the printing.  Neither ``overrides`` nor ``allow_cpu``
    can be passed from the command line.  ``overrides`` ({"config": ...,
    "traffic": ...}, merged into the cell's files) is for the tests; its
    result is marked ``"overrides": true`` and is no contract line.
    ``allow_cpu`` is for the CPU rehearsal in ``benchmark/tests`` alone:
    such a result is marked ``"rehearsal"`` so that no CPU number is ever
    read as a device metric."""
    bench, ctx, dev = make_context(workload, seed, seconds, trace, overrides,
                                   allow_cpu)
    devices, clock, config = ctx.devices, ctx.clock, ctx.config

    res = spec.module("runners", config["runner"]).run(ctx)
    setup_s = res["t_window_start"] - _T_PROCESS_START
    built = clock.mark()
    _log(f"{workload}: set-up {setup_s:.2f} s; the process built or loaded "
         f"{built[0]} programs in {built[1]:.2f} s in all")

    values: Dict[str, Optional[float]] = dict(res["end_to_end"])
    values["setup_s"] = setup_s
    dev["memory_peak_bytes"] = device.memory_peak_bytes(devices)
    out: Dict[str, Any] = {
        "correct": bool(res["correct"]), "attempted": int(res["attempted"]),
        "failed": int(res["failed"]), "metrics": {}, "device": dev}
    if not trace:
        for m in spec.metrics_for(bench, "end_to_end", workload):
            if values.get(m["name"]) is not None:
                out["metrics"][m["name"]] = {"value": values[m["name"]],
                                             "unit": m["unit"]}
    else:
        _reduce_trace(bench, workload, res["facts"], ctx, out)
    if overrides:
        out["overrides"] = True
    if dev["platform"] != "tpu":
        out["rehearsal"] = True
    out["_facts"] = res["facts"]        # for tools and tests; never printed
    return out


def _reduce_trace(bench, workload, facts, ctx, out) -> None:
    """Per-layer metrics: each from its own reader over the trace view and
    the runner's facts; a reader that finds nothing returns None and the
    metric is left out of the line."""
    from benchmark.lib import tracing

    cap = facts.get("capture") or {}
    view = None
    if cap.get("xplane"):
        t0 = time.monotonic()
        view = tracing.TraceView.from_xplane(cap["xplane"])
        _log(f"trace: {os.path.getsize(cap['xplane']) / 1e6:.1f} MB, "
             f"{len(view.device_events)} device events on "
             f"{len(view.devices)} device(s), {len(view.host_events)} host "
             f"events, read in {time.monotonic() - t0:.1f} s")
    if view is not None and not view.device_events:
        view = None             # nothing ran on a device: nothing to read
    facts["view"] = view
    reported = {m["name"]
                for m in spec.metrics_for(bench, "end_to_end", workload)}
    for m in spec.metrics_for(bench, "per_layer", workload):
        if m["moves"] not in reported:
            continue
        how = spec.layer_metric_file(m["name"])
        value = spec.module("readers", how["reader"]).read(
            facts, how.get("args", {}), ctx)
        if value is not None:
            out["metrics"][m["name"]] = {"value": float(value),
                                         "unit": m["unit"]}
    if view is not None:
        lo, hi = view.window()
        out["device"]["busy_s"] = view.busy_seconds()
        out["device"]["window_s"] = (hi - lo) / 1e9
        labels = spec.module("readers", "_host_labels").labels(facts)
        out["breakdown"] = {"device_ops": view.top_ops(10),
                            "idle_gaps": view.idle_gaps(labels, n=10)}


def _merge(into: dict, over: dict) -> None:
    for k, v in over.items():
        if isinstance(v, dict) and isinstance(into.get(k), dict):
            _merge(into[k], v)
        else:
            into[k] = v


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    args = ap.parse_args(argv)
    out = run_cell(args.workload, args.seed, args.seconds, bool(args.trace))
    out.pop("_facts")
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
