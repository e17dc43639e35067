"""Device milliseconds per WHOLE execution of a step program launched by a
tick of one ``kind``: ``busy`` = the device time of the execution's
operations; ``idle`` = how long the first device sat idle between the end of
the execution before it and its start, charged to the kind of the tick that
launched it, the stretch's ONE longest gap left out (most traced stretches
hold one stall of about 100 ms that no untraced run shows; it is logged on
a line of its own).  Executions are the events of the profile's "XLA
Modules" line, joined to the program's launch record by program name and
order, anchored in time (``readers/_launches.py``): no number is a
difference between the host's and the device's plane, so the planes' skew
does not move them, and the stretch's first and last execution are left
out, so the mix of ticks the stretch caught does not either.  Logs once a
run: program -> whole executions, busy ms an execution (mean, least -
most), idle ms before one; the executions left out (at the stretch's ends;
beside launches the profile lost); the launches of the stretch that found
no execution (must be 0); the skew the join saw; the longest gap; and once
a kind, ``idle`` less the starved host time of the same launches (what the
host does not explain: the result's transfer and the runtime's latency) and
the share of the kind's executions the device waited for.  None without a
trace or a launch record.  args: kind, what (busy|idle)."""

import collections

from benchmark.readers import _host_labels, _launches

#: a gap this long before an execution means the device waited for it: the
#: runtime starts a program queued behind another within 2-8 us (PR 33)
WAITED_NS = 100_000


def _mean(xs, key):
    return sum(x[key] for x in xs) / len(xs) / 1e6 if xs else float("nan")


def _log_table(execs, info, ctx):
    table = collections.OrderedDict()
    for x in execs:
        if not x["cut"]:
            table.setdefault(x["program"], []).append(x)
    gaps = _launches.idle_gaps(execs, info)
    counted = {id(x) for x in gaps}
    gap_ms = {p: _mean([x for x in v if id(x) in counted], "idle_before")
              for p, v in table.items()}
    ctx.log("launches: program -> whole executions, busy ms an execution "
            "(least - most), idle ms before one: " + "; ".join(
                f"{p} {len(v)}, {_mean(v, 'busy'):.3f} "
                f"({min(x['busy'] for x in v) / 1e6:.2f} - "
                f"{max(x['busy'] for x in v) / 1e6:.2f}), "
                f"{gap_ms[p]:.3f}"
                for p, v in sorted(table.items(),
                                   key=lambda kv: -len(kv[1]))))
    stretch = execs[-1]["end"] - execs[0]["start"]
    inside = sum(x["end"] - x["start"] - x["busy"] for x in execs)
    between = stretch - sum(x["end"] - x["start"] for x in execs)
    ctx.log(f"launches: {info['ends']} execution(s) left out at the "
            f"stretch's ends and {info['beside_lost']} beside launches the "
            f"profile lost, {len(info['missing'])} launch(es) of the "
            f"stretch without an execution {info['missing'][:8]}, "
            f"{info['unjoined']} whole execution(s) without a launch; the "
            f"device plane would have to move {info['skew_ns'] / 1e6:.3f} "
            f"ms later for no execution to start before its dispatch "
            f"opened; of {(between + inside) / 1e9:.4f} s idle in a stretch "
            f"of {stretch / 1e9:.4f} s, {between / 1e9:.4f} s between "
            f"executions and {inside / 1e9:.4f} s inside them")
    worst = info["longest"]
    if worst is not None:
        row = worst["launch"] or {}
        ctx.log(f"launches: the longest gap of the stretch, "
                f"{worst['idle_before'] / 1e6:.3f} ms before launch "
                f"{row.get('launch')} ({worst['program']}, a "
                f"{row.get('kind')} tick), is left out of every idle "
                f"figure: the mean over all {len(gaps) + 1} gaps is "
                f"{_mean(gaps + [worst], 'idle_before'):.3f} ms with it, "
                f"{_mean(gaps, 'idle_before'):.3f} without")


def _log_kind(facts, kind, mine, gaps, ctx):
    """``idle`` beside the starved host time of the same launches."""
    off = _host_labels.offset_ns(facts)
    ends = {n: (s + off, e + off) for s, e, n in _launches.starved(facts)}
    n = max(len(gaps), 1)
    idle = sum(x["idle_before"] for x in gaps)
    host = late = early = 0
    for x in gaps:
        s, e = ends.get(x["launch"]["launch"], (0, 0))
        host += e - s
        if e:
            # the wait ended this long after the execution before did (the
            # result's transfer); this one started this long after its
            # dispatch span closed (negative: before it closed)
            late += s - (x["start"] - x["idle_before"])
            early += x["start"] - e
    waited = sum(x["idle_before"] > WAITED_NS for x in gaps)
    ctx.log(f"launches of {kind} ticks: {len(mine)} whole executions in the "
            f"stretch, the device waited more than {WAITED_NS / 1e6:.1f} ms "
            f"for {100.0 * waited / n:.1f}% of them; idle before one "
            f"{idle / n / 1e6:.3f} ms, of "
            f"which the host starved the device {host / n / 1e6:.3f} ms and "
            f"{(idle - host) / n / 1e6:.3f} ms are not explained by the "
            f"host: the wait that ended a starved interval returned "
            f"{late / n / 1e6:.3f} ms after the execution before it ended, "
            f"the next execution started {early / n / 1e6:.3f} ms after its "
            f"dispatch span closed (each of the two moves with the planes' "
            f"skew, their sum does not), the rest are gaps between queued "
            f"programs")


def read(facts, args, ctx):
    execs, info = _launches.joined(facts)
    if not execs:
        return None
    if "_launch_table_logged" not in facts:
        facts["_launch_table_logged"] = True
        _log_table(execs, info, ctx)
    kinds = set(args["kind"].split("+"))
    mine = [x for x in execs if not x["cut"] and x["launch"] is not None
            and x["launch"]["kind"] in kinds]
    gaps = _launches.idle_gaps(mine, info)
    vals = [x["idle_before"] for x in gaps] if args["what"] == "idle" \
        else [x["busy"] for x in mine]
    if not vals:
        return None
    logged = "_launch_kind_logged/" + args["kind"]
    if logged not in facts:
        facts[logged] = True
        _log_kind(facts, args["kind"], mine, gaps, ctx)
    return sum(vals) / len(vals) / 1e6
