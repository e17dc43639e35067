"""Mean host milliseconds, per window tick of one ``kind``, in which the
program had given the device nothing to do: no launch outstanding, from the
end of the wait that retired the last outstanding launch (``fetch`` /
``engine/fetch_logits``, each naming its ``launch``) to the end of the next
dispatch span.  Each such interval is charged to the tick that made that
next dispatch.  Host clock, the whole window, no profiler
(``readers/_launches.py``).  A LOWER bound on the device's idle time (the
transfer of the result and the runtime's own latency are not in it) and
exact as "time the host gave the chip nothing to do".  Logs, once a run and
kind, that time by the deepest Tracer span covering it (a span's self
time; ``between ticks`` = the caller's loop).  None for a program that records no launches.  args: kind."""

from benchmark.readers import _launches, _tick_tree


def read(facts, args, ctx):
    kind = args["kind"]
    ticks = _tick_tree.kind_ticks(facts, kind)
    launches = _launches.rows(facts)
    if not ticks or not launches:
        return None
    ids = {t["span_id"] for t in ticks}
    tick_of = {r["launch"]: r["tick"] for r in launches}
    mine = [(s, e) for s, e, n in _launches.starved(facts)
            if tick_of.get(n) in ids]
    ns = sum(e - s for s, e in mine)
    key = "_launch_starved_logged/" + kind
    if key not in facts:
        facts[key] = True
        n = len(ticks)
        sent = sum(r["tick"] in ids for r in launches)
        split = sorted(_launches.split_by_span(facts, mine).items(),
                       key=lambda kv: -kv[1])
        ctx.log(f"{n} {kind} ticks in the window made {sent} launches; "
                f"starved {ns / n / 1e6:.3f} ms a tick over "
                f"{len(mine)} intervals, by the span covering it: "
                + ", ".join(f"{k} {v / n / 1e6:.3f}" for k, v in split))
    return ns / len(ticks) / 1e6
