"""Shared by the readers: the traced stretch on the profiler's clock, and
what the host was doing in it.

``offset_ns`` moves ``time.monotonic_ns`` stamps (the harness's own request
records, the program's Tracer spans) onto the profiler's clock, through the
``bench/clock_sync`` span whose start was read on both.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Tuple

_LABELLED = r"^(bench/(?!clock_sync)|engine/|ds_tick)"


def offset_ns(facts) -> Optional[int]:
    view, cap = facts.get("view"), facts.get("capture") or {}
    if view is None or "mono_sync_ns" not in cap:
        return None
    sync = view.host_named(r"^bench/clock_sync$")
    if not sync:
        return None
    return sync[0].start - int(cap["mono_sync_ns"])


def tracer_spans(facts) -> List[dict]:
    """The program's Tracer records that are closed spans."""
    return [r for r in facts.get("tracer_records", ()) if r.get("ph") == "X"]


def ticks(facts) -> List[Dict]:
    """Scheduler ticks from the Tracer: ``{"t0", "t1", "phases": {name:
    ns}}`` on ``time.monotonic_ns``, for ticks that packed a batch."""
    spans = tracer_spans(facts)
    kids: Dict[str, Dict[str, int]] = {}
    for r in spans:
        if r.get("parent"):
            d = kids.setdefault(r["parent"], {})
            d[r["name"]] = d.get(r["name"], 0) + r["t1_ns"] - r["t0_ns"]
    out = []
    for r in spans:
        if r["name"] == "tick":
            ph = kids.get(r["span_id"], {})
            if any(k in ph for k in ("prefill", "decode", "verify")):
                out.append({"t0": r["t0_ns"], "t1": r["t1_ns"], "phases": ph})
    return out


def window_ticks(facts) -> List[Dict]:
    """Ticks inside the measured window (the Tracer also saw the pre-roll
    and the drain)."""
    w0 = facts["t_start_ns"]
    w1 = facts["t_stop_ns"]
    return [t for t in ticks(facts) if w0 <= t["t0"] and t["t1"] <= w1]


def labels(facts) -> List[Tuple[str, int, int]]:
    """(name, start, end) spans on the profiler's clock for labelling idle
    gaps: the harness's and the program's profiler annotations, plus the
    scheduler's tick phases moved over from the Tracer's clock."""
    view = facts.get("view")
    if view is None:
        return []
    out = [(e.name, e.start, e.end) for e in view.host_named(_LABELLED)]
    off = offset_ns(facts)
    if off is not None:
        for r in tracer_spans(facts):
            if r.get("parent"):
                out.append(("tick/" + r["name"], r["t0_ns"] + off,
                            r["t1_ns"] + off))
    return out
