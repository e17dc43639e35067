"""obs_dump — render and validate the observability exports.

Runs a tiny traced scheduler workload (or takes an existing trace file),
writes the Chrome/Perfetto trace JSON plus the unified registry's
Prometheus exposition, and validates the trace-event schema:

* every event is a complete span ("X"), a matched begin/end pair
  ("B"/"E" sharing a ``span_id``), an instant ("i"), or metadata ("M");
* every span/instant carries ``args.trace_id`` (it belongs to a known
  trace) and a unique ``args.span_id``;
* every ``args.parent`` refers to a span_id that exists in the SAME
  trace (no orphaned children, no cross-trace parents);
* durations are non-negative.

Worker flight rings (``flight.<attempt>.json``, the crash-durable span
tails the front-end folds into postmortems) get their own validator —
:func:`validate_flight` checks the schema envelope, span fields,
monotonic ring order, and the attempt-suffix ↔ incarnation-tag match,
so a torn or mis-tagged flight file fails loudly in tier-1.

Wired into tier-1 via ``tests/unit/test_observability.py`` against a
tiny scheduler run.  Standalone::

    JAX_PLATFORMS=cpu python tools/obs_dump.py --out /tmp/obs
    python tools/obs_dump.py --validate trace.json
    python tools/obs_dump.py --validate-flight run/replica0/flight.1.json
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time
from typing import Dict, List, Optional

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


# --------------------------------------------------------------------- #
# Schema validation
# --------------------------------------------------------------------- #
def validate_trace(events: List[dict]) -> List[str]:
    """Validate trace-event dicts (a ``traceEvents`` list or a tracer's
    ``export_events`` output).  Returns a list of problems — empty means
    the trace is loadable and internally consistent."""
    problems: List[str] = []
    spans: Dict[str, dict] = {}          # span_id -> event (X or B)
    begins: Dict[str, dict] = {}
    ends: Dict[str, dict] = {}
    payload = [e for e in events if e.get("ph") != "M"]
    for i, e in enumerate(payload):
        ph = e.get("ph")
        where = f"event {i} ({e.get('name')!r})"
        if ph not in ("X", "B", "E", "i"):
            problems.append(f"{where}: unknown phase {ph!r}")
            continue
        args = e.get("args") or {}
        if not args.get("trace_id"):
            problems.append(f"{where}: no args.trace_id — span belongs "
                            "to no known trace")
            continue
        sid = args.get("span_id")
        if not sid:
            problems.append(f"{where}: no args.span_id")
            continue
        if ph in ("X", "B"):
            if sid in spans:
                problems.append(f"{where}: duplicate span_id {sid}")
            spans[sid] = e
        if ph == "B":
            begins[sid] = e
        elif ph == "E":
            if sid in ends:
                problems.append(f"{where}: duplicate end for {sid}")
            ends[sid] = e
        if ph == "X" and float(e.get("dur", -1.0)) < 0:
            problems.append(f"{where}: X event without dur >= 0")
    # B/E pairing by span_id
    for sid, e in begins.items():
        if sid not in ends:
            problems.append(f"span {sid} ({e.get('name')!r}): B without "
                            "matching E")
    for sid, e in ends.items():
        if sid not in begins:
            problems.append(f"span {sid} ({e.get('name')!r}): E without "
                            "matching B")
    # parent links resolve within the same trace
    for sid, e in spans.items():
        args = e.get("args") or {}
        parent = args.get("parent")
        if parent is None:
            continue
        pe = spans.get(parent)
        if pe is None:
            problems.append(
                f"span {sid} ({e.get('name')!r}): parent {parent} does "
                "not exist")
        elif (pe.get("args") or {}).get("trace_id") != args.get("trace_id"):
            problems.append(
                f"span {sid} ({e.get('name')!r}): parent {parent} lives "
                "in a different trace")
    # instants' parents too
    for i, e in enumerate(payload):
        if e.get("ph") != "i":
            continue
        args = e.get("args") or {}
        parent = args.get("parent")
        if parent is not None and parent not in spans:
            problems.append(f"instant {i} ({e.get('name')!r}): parent "
                            f"{parent} does not exist")
    return problems


def validate_flight(path: str, attempt: Optional[int] = None
                    ) -> List[str]:
    """Validate a worker's crash-durable ``flight.<attempt>.json`` ring
    (the FlightRecorder's atomic flush).  A torn/mis-tagged flight file
    must fail LOUDLY here — the front-end's postmortems are built from
    these after a SIGKILL, so quiet corruption poisons the evidence.

    Checks: the ``ds-flight-v1`` schema envelope; span-record fields
    (name/ph/ts, ``args.span_id`` unique, non-negative durations);
    monotonic ring order (closed spans land in finish order — their end
    timestamps must be non-decreasing); and the filename's ``.<attempt>.``
    suffix matching every ``<replica>#<incarnation>`` span tid (a respawn
    writing into its predecessor's ring would interleave incarnations).
    Parent links are NOT required to resolve — the ring is a tail, and a
    parent may have been legitimately evicted."""
    problems: List[str] = []
    if attempt is None:
        base = os.path.basename(path)
        parts = base.split(".")
        if len(parts) >= 3 and parts[-1] == "json" \
                and parts[-2].isdigit():
            attempt = int(parts[-2])
    try:
        with open(path) as f:
            data = json.load(f)
    except OSError as e:
        return [f"{path}: unreadable: {e}"]
    except ValueError as e:
        return [f"{path}: torn/unparseable JSON: {e}"]
    if not isinstance(data, dict) \
            or data.get("schema") != "ds-flight-v1":
        return [f"{path}: not a ds-flight-v1 flight ring "
                f"(schema={data.get('schema') if isinstance(data, dict) else type(data).__name__!r})"]
    for field in ("wall_time", "ticks", "spans"):
        if field not in data:
            problems.append(f"missing field {field!r}")
    spans = data.get("spans", [])
    if not isinstance(spans, list):
        return problems + [f"spans is {type(spans).__name__}, not a list"]
    def num(v):
        try:
            return float(v)
        except (TypeError, ValueError):
            return None

    seen_ids: set = set()
    last_end = None
    for i, e in enumerate(spans):
        if not isinstance(e, dict):
            # a torn/doctored ring must report, never raise — this IS
            # the "fails loudly" contract
            problems.append(
                f"span {i}: not an object ({type(e).__name__})")
            continue
        where = f"span {i} ({e.get('name')!r})"
        if e.get("ph") == "M":
            continue
        for field in ("name", "ph", "ts", "tid"):
            if field not in e:
                problems.append(f"{where}: missing {field!r}")
        args = e.get("args") if isinstance(e.get("args"), dict) else {}
        sid = args.get("span_id")
        if not sid:
            problems.append(f"{where}: no args.span_id")
        elif sid in seen_ids and e.get("ph") in ("X", "B", "i"):
            problems.append(f"{where}: duplicate span_id {sid}")
        else:
            seen_ids.add(sid)
        if e.get("ph") == "X":
            dur = num(e.get("dur", -1.0))
            ts = num(e.get("ts", 0.0))
            if dur is None or dur < 0:
                problems.append(f"{where}: X event without dur >= 0")
            if ts is None:
                problems.append(f"{where}: non-numeric ts "
                                f"{e.get('ts')!r}")
            elif dur is not None and not args.get("unfinished"):
                end = ts + max(dur, 0.0)
                if last_end is not None and end < last_end - 1e-3:
                    problems.append(
                        f"{where}: ring order broken — finish ts "
                        f"{end:.3f} precedes previous {last_end:.3f} "
                        "(timestamps must be monotonic in ring order)")
                last_end = max(last_end or end, end)
        tid = str(e.get("tid", ""))
        if attempt is not None and "#" in tid:
            inc = tid.rsplit("#", 1)[1]
            if inc.isdigit() and int(inc) != attempt:
                problems.append(
                    f"{where}: incarnation tag {tid!r} does not match "
                    f"flight attempt suffix .{attempt}.")
    return problems


def trace_summary(events: List[dict]) -> dict:
    payload = [e for e in events if e.get("ph") != "M"]
    traces = {(e.get("args") or {}).get("trace_id") for e in payload}
    names: Dict[str, int] = {}
    for e in payload:
        names[e["name"]] = names.get(e["name"], 0) + 1
    return {"events": len(payload), "traces": len(traces - {None}),
            "names": names}


# --------------------------------------------------------------------- #
# The tiny traced run (tier-1's subject)
# --------------------------------------------------------------------- #
def run_traced_sample(out_dir: str, n_requests: int = 4,
                      seed: int = 0) -> dict:
    """Drive a few requests through a traced tiny-Llama scheduler with
    the unified registry attached; write ``trace.json`` +
    ``metrics.prom``; validate both.  Returns the summary dict."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from deepspeed_tpu.inference.v2 import (InferenceEngineV2,
                                            RaggedInferenceEngineConfig)
    from deepspeed_tpu.inference.v2.model_implementations import RaggedLlama
    from deepspeed_tpu.models import LlamaConfig, LlamaForCausalLM
    from deepspeed_tpu.observability import (MetricsRegistry, Tracer,
                                             merge_events, process_tracer,
                                             write_chrome_trace)
    from deepspeed_tpu.serving import (ContinuousBatchScheduler,
                                       SamplingParams)

    cfg = LlamaConfig.tiny(dtype=jnp.float32)
    params = LlamaForCausalLM(cfg).init(
        jax.random.key(0), np.zeros((1, 4), np.int32))["params"]
    eng_cfg = RaggedInferenceEngineConfig.from_dict({
        "state_manager": {"max_ragged_batch_size": 32,
                          "max_ragged_sequence_count": 4,
                          "max_context": 48},
        "kv_cache": {"block_size": 8, "num_blocks": 17},
    })
    engine = InferenceEngineV2(RaggedLlama(cfg, 8), params, eng_cfg)
    tracer = Tracer(tid="replica0")
    registry = MetricsRegistry()
    sched = ContinuousBatchScheduler(engine, tracer=tracer,
                                     registry=registry)
    rng = np.random.default_rng(seed)
    reqs = [sched.submit(
        rng.integers(0, cfg.vocab_size, size=(int(n),)).tolist(),
        sampling=SamplingParams(greedy=True, max_new_tokens=6))
        for n in rng.integers(8, 16, size=n_requests)]
    sched.run_until_idle()
    assert all(r.state.value == "finished" for r in reqs), \
        [(r.uid, r.state.value) for r in reqs]

    os.makedirs(out_dir, exist_ok=True)
    # one timeline: what the process did once (setup/import, the engine's
    # constructor, one setup/build_program a program built) and the ticks
    events = merge_events(process_tracer().export_events(),
                          tracer.export_events())
    trace_path = os.path.join(out_dir, "trace.json")
    write_chrome_trace(trace_path, events)
    problems = validate_trace(events)
    assert not problems, problems

    # registry exposition: declared names typed, values from the live
    # scheduler provider
    prom = registry.to_prometheus()
    prom_path = os.path.join(out_dir, "metrics.prom")
    with open(prom_path, "w") as f:
        f.write(prom)
    assert "serving_finished" in prom, prom[:400]
    assert not registry.unknown_names, registry.unknown_names

    # every request's spans connect: submit -> queued -> prefill -> decode
    # under one trace_id, parents resolving
    for r in reqs:
        mine = [e for e in events
                if (e.get("args") or {}).get("trace_id") == r.trace_id]
        names = {e["name"] for e in mine}
        assert {"request/submit", "request/queued", "request/prefill",
                "request/decode"} <= names, (r.uid, names)

    summary = trace_summary(events)
    return {"obs_dump": "ok", "trace_path": trace_path,
            "prom_path": prom_path, "schema_problems": 0,
            "events": summary["events"], "traces": summary["traces"],
            "prom_lines": prom.count("\n")}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="obs_dump", description="render + validate observability "
                                     "exports")
    ap.add_argument("--out", default=None,
                    help="output dir for trace.json/metrics.prom "
                         "(default: a temp dir)")
    ap.add_argument("--validate", default=None,
                    help="validate an existing trace JSON instead of "
                         "running the sample workload")
    ap.add_argument("--validate-flight", default=None,
                    help="validate a worker flight.<attempt>.json ring")
    args = ap.parse_args(argv)

    if args.validate_flight is not None:
        problems = validate_flight(args.validate_flight)
        print(json.dumps({
            "obs_dump": "ok" if not problems else "invalid",
            "flight": args.validate_flight,
            "schema_problems": len(problems),
            "problems": problems[:20]}))
        return 0 if not problems else 1

    if args.validate is not None:
        from deepspeed_tpu.observability import load_chrome_trace

        events = load_chrome_trace(args.validate)
        problems = validate_trace(events)
        print(json.dumps({"obs_dump": "ok" if not problems else "invalid",
                          "schema_problems": len(problems),
                          "problems": problems[:20],
                          **trace_summary(events)}))
        return 0 if not problems else 1

    t0 = time.monotonic()
    out_dir = args.out or tempfile.mkdtemp(prefix="obs_dump_")
    summary = run_traced_sample(out_dir)
    summary["wall_s"] = round(time.monotonic() - t0, 2)
    print(json.dumps(summary))
    return 0


if __name__ == "__main__":
    sys.exit(main())
