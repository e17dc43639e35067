"""Shared by ``span_ms_tick`` and ``tick_attr_ratio``: the scheduler ticks
of the measured window as the program's Tracer recorded them, selected by
the ``kind`` each ``tick`` span closed with, and the spans that descend
from them with the counters those spans own.

Whole window, host clock (``time.monotonic_ns``): nothing here needs the
profiler, so the numbers do not depend on which seconds were traced.  A
program whose ticks carry no ``kind`` (before PR 23) has no tick of any
kind: the readers then return None and the metric is left out.
"""

from __future__ import annotations

from typing import List, Tuple

from benchmark.readers import _host_labels


def kind_ticks(facts, kind: str) -> List[dict]:
    """The ``tick`` records inside the window whose ``kind`` is one of
    ``kind`` (names joined by ``+``: ``mixed+prefill`` = every tick that
    ran a ragged batch, with or without a decoding sequence in it, what the
    harness calls a mixed tick)."""
    w0, w1 = facts.get("t_start_ns"), facts.get("t_stop_ns")
    if w0 is None or w1 is None:
        return []
    kinds = set(kind.split("+"))
    return [r for r in _host_labels.tracer_spans(facts)
            if r["name"] == "tick" and w0 <= r["t0_ns"] and r["t1_ns"] <= w1
            and (r.get("attrs") or {}).get("kind") in kinds]


def descendants(facts, kind: str) -> Tuple[List[dict], List[dict]]:
    """(the window's ticks of ``kind``, every span record whose chain of
    parents reaches one of them).  Computed once a run and kind."""
    key = "_tick_tree/" + kind
    if key not in facts:
        ticks = kind_ticks(facts, kind)
        roots = {t["span_id"] for t in ticks}
        spans = _host_labels.tracer_spans(facts)
        parent = {r["span_id"]: r.get("parent") for r in spans}
        under = []
        for r in spans:
            up = r.get("parent")
            while up is not None and up not in roots:
                up = parent.get(up)
            if up is not None:
                under.append(r)
        facts[key] = (ticks, under)
    return facts[key]
