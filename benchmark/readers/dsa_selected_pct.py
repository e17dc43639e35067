"""Positions the sparse read takes over positions the indexer scores, in
percent, over the window: ``(sel_keys + sel_pairs) / (idx_keys +
idx_pairs)`` from the program's own counters on the ``decode`` spans (the
consumed decode step's rows) and on ``engine/build_batch`` (a ragged
batch's one-token rows and its chunks' rows) that start inside the measured
window.  100 would mean the traffic never passes ``index_topk`` and the cell
measures dense attention.  None when the program records no such counter."""

from benchmark.lib import costs_dsa
from benchmark.readers import _host_labels


def read(facts, args, ctx):
    w0, w1 = facts.get("t_start_ns"), facts.get("t_stop_ns")
    if w0 is None or w1 is None:
        return None
    idx = sel = 0
    for r in _host_labels.tracer_spans(facts):
        a = r.get("attrs") or {}
        if "idx_keys" in a and w0 <= r["t0_ns"] <= w1:
            idx += a["idx_keys"] + a.get("idx_pairs", 0)
            sel += a["sel_keys"] + a.get("sel_pairs", 0)
    return costs_dsa.selected_pct(idx, sel)
