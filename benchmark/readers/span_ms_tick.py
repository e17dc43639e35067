"""Mean host milliseconds, per window tick of one ``kind``, of the program's
Tracer spans of the given names that descend from that tick (whatever the
depth: ``engine/decode_prep`` lies under the tick's ``decode`` phase).  Over
every tick of the measured window, not the traced stretch.  Logs, once a
run and kind, the mean of every span under such ticks: the tick's host time
by name.  args: names, kind."""

from benchmark.readers import _tick_tree


def read(facts, args, ctx):
    kind = args["kind"]
    logged = "_tick_tree/" + kind in facts
    ticks, under = _tick_tree.descendants(facts, kind)
    n = len(ticks)
    if not n:
        return None
    ns = {}
    for r in under:
        ns[r["name"]] = ns.get(r["name"], 0) + r["t1_ns"] - r["t0_ns"]
    if not logged:
        ns_tick = sum(t["t1_ns"] - t["t0_ns"] for t in ticks)
        ctx.log(f"{n} {kind} ticks in the window, host ms per tick by "
                f"span: tick {ns_tick / n / 1e6:.3f}, " + ", ".join(
                    f"{k} {v / n / 1e6:.3f}" for k, v in
                    sorted(ns.items(), key=lambda kv: -kv[1])))
    return sum(ns.get(name, 0) for name in args["names"]) / n / 1e6
