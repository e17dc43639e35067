"""100 x (a counter of the program, averaged over the window's ticks
weighted by their length) / (a total from the family's shapes): e.g. state
slots held over slots, as ``kv_live_pct`` is blocks held over blocks.  A
tick's value is the counter on the first span under it that owns one
(``engine/decode_prep`` or ``engine/build_batch``); ticks without one (a
tick that only returned the tokens of a step dispatched a tick before) are
left out of both sums.  Whole window, host clock.  None when no tick
carries the counter or the shapes lack the total.
args: attr, total (a key of ``shapes``)."""

from benchmark.readers import _tick_tree


def read(facts, args, ctx):
    total = (facts.get("shapes") or {}).get(args["total"])
    if not total:
        return None
    ticks, under = _tick_tree.descendants(
        facts, "decode+mixed+prefill+verify")
    parent = {r["span_id"]: r.get("parent") for r in under}
    roots = {t["span_id"]: t for t in ticks}
    seen, num, den = set(), 0.0, 0.0
    for r in under:
        value = (r.get("attrs") or {}).get(args["attr"])
        if value is None:
            continue
        up = r.get("parent")
        while up is not None and up not in roots:
            up = parent.get(up)
        if up is None or up in seen:
            continue
        seen.add(up)
        dur = roots[up]["t1_ns"] - roots[up]["t0_ns"]
        num += dur * value
        den += dur
    return 100.0 * num / (den * total) if den else None
