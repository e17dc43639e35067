"""A request's first token, in the four parts the program itself records
(PR 48), over the requests submitted inside the measured window that
reached a first token without a preemption, whole window, on the ring's
clock (``time.monotonic_ns``):

========  ==================================================  =============
phase     from -> to                                          owner
========  ==================================================  =============
queued    ``request/queued`` opens (``submit``) -> closes     admission:
          (``_admit``)                                        budget, rows,
                                                              slots, KV
behind    ``request/prefill`` opens -> the estimated start    the program in
          of ``first_launch`` on the device: the later of     flight ahead of
          the end of its dispatch span and the end of the     it,
          wait that retired the launch before it              ``prepare``,
                                                              upload,
                                                              dispatch
prefill   that start -> the end of the wait that retired      the prompt's
          ``last_launch`` (``_launches.rows``)                own chunks and
                                                              the ticks
                                                              between them
handout   that wait's end -> ``request/decode`` opens (no     the advance of
          such record, because the request ends with its      the rows before
          first token or still decodes when the ring is       it, a launch
          read: ``request/prefill`` closes)                   made before the
                                                              tokens go out
========  ==================================================  =============

The four add up to first token less submit by construction but for the few
clock reads between a phase's close and the next one's open; a request whose
parts miss that span by more than ``TOL_NS`` is left out and counted.
``hold_ticks``: the ticks that packed a batch (a ``pack`` span that closed
with ``queued`` > 0) while the request's ``request/queued`` span was open;
``chunks``: the step programs that carried its prompt (``request/prefill``
closes with ``chunks``, ``first_launch``, ``last_launch``,
``behind_launch``).

Logs once a run: the requests kept and those left out, by cause; every
phase's p50 / p90 / mean and the median of their sum beside the harness's
``ttft_ms`` and ``gen_late_ms`` medians; the harness's own stamps of the
matched requests (by submit order: the caller and the scheduler are one
thread) against the sum, and two checks of the match (``prompt_tokens`` of
``request/submit``, ``tokens`` of ``request/decode``); the held ticks by
``held_by``; the requests that arrived behind a launch in flight
(``behind_launch``) beside those that found the device level; how the
window's requests ended; and, for the requests whose first and last launch
were joined to whole executions of the traced stretch (``_launches.join``),
the error of the host's estimate of ``behind`` and ``prefill`` against the
executions' own start and end, moved onto the ring's clock with
``_host_labels.offset_ns`` (``behind`` then holds the planes' skew;
``prefill`` is a difference on one plane each way).

None on a program without ``request/queued`` spans (the parent of PR 48).  args: what (queued |
behind | prefill | handout: with ``q``, a percentile; hold_ticks | chunks:
the mean), q."""

from __future__ import annotations

import bisect
import collections
from typing import Dict, List, Optional

from benchmark.lib import stats
from benchmark.readers import _host_labels, _launches

#: how far a request's four parts may miss first token less submit
TOL_NS = 100_000
PHASES = ("queued", "behind", "prefill", "handout")
NOT_PACKED = "not packed"


def _holds(facts):
    """(ends, [(end, start, tick span id, held_by, queued)]) of the ``pack``
    spans that left someone waiting, by their end."""
    packs = sorted(
        (r["t1_ns"], r["t0_ns"], r.get("parent"),
         r["attrs"].get("held_by", NOT_PACKED), r["attrs"]["queued"])
        for r in _host_labels.tracer_spans(facts)
        if r["name"] == "pack" and (r.get("attrs") or {}).get("queued"))
    return [p[0] for p in packs], packs


def _chains(facts) -> Dict[str, List[dict]]:
    """{trace_id: its ``request/*`` records, oldest first} of the requests
    submitted inside the window (the ``request/submit`` instant, which
    ``request/queued`` opens beside, lies in it)."""
    w0, w1 = facts["t_start_ns"], facts["t_stop_ns"]
    by_trace: Dict[str, List[dict]] = collections.defaultdict(list)
    for r in facts.get("tracer_records", ()):
        if r["name"].startswith("request/"):
            by_trace[r["trace_id"]].append(r)
    out = {}
    for trace, recs in by_trace.items():
        recs.sort(key=lambda r: r["t0_ns"])
        if recs[0]["name"] == "request/submit" \
                and w0 <= recs[0]["t0_ns"] < w1:
            out[trace] = recs
    return out


def _phases(spans, launches) -> Optional[dict]:
    """The four parts of one clean chain (queued, prefill[, decode]), or
    the cause it is left out for."""
    if not spans:
        return {"left": "no first token"}       # still in the queue
    names = [r["name"] for r in spans]
    attrs = [r.get("attrs") or {} for r in spans]
    if any(a.get("outcome") == "preempted" for a in attrs[:2]):
        return {"left": "preempted"}
    if names[:2] != ["request/queued", "request/prefill"]:
        # failed in the queue
        return {"left": "failed" if attrs[0].get("outcome") == "failed"
                else "no first token"}
    q, p, a = spans[0], spans[1], attrs[1]
    if a.get("outcome", "finished") != "finished":
        return {"left": "failed" if a["outcome"] == "failed"
                else "no first token"}          # handed off, aborted
    # ``request/prefill`` closes on the first token: the decode phase opens
    # beside it (still open when the ring was read: no record yet), or the
    # request ends with that token
    first_token = spans[2]["t0_ns"] if names[2:3] == ["request/decode"] \
        else p["t1_ns"]
    first, last = launches.get(a.get("first_launch")), \
        launches.get(a.get("last_launch"))
    if first is None or last is None or last["r1"] is None:
        return {"left": "no launch record"}
    start = first["d1"]
    before = launches.get(first["launch"] - 1)
    # (the same wait retired both: the one before is not known to have
    # ended before this one was dispatched)
    if before is not None and before["r1"] is not None \
            and before["r1"] != first["r1"]:
        start = max(start, before["r1"])
    out = {"queued": q["t1_ns"] - q["t0_ns"], "behind": start - p["t0_ns"],
           "prefill": last["r1"] - start, "handout": first_token - last["r1"]}
    out["sum"] = sum(out.values())
    if abs(out["sum"] - (first_token - q["t0_ns"])) > TOL_NS:
        return {"left": "parts do not add up"}
    out.update(t0=q["t0_ns"], t1=q["t1_ns"], p0=p["t0_ns"], chunks=a["chunks"],
               first=first, last=last, behind_launch=a["behind_launch"],
               end=spans[-1].get("attrs") or {})
    return out


def requests(facts) -> Optional[dict]:
    """{"kept": [a dict a request], "left": Counter by cause, "n": the
    requests submitted in the window, "launches": {number: its row},
    "depths": ``queued`` of the window's packs that left someone waiting},
    once a run; None without the spans."""
    if "_request_phases" not in facts:
        facts["_request_phases"] = _requests(facts)
    return facts["_request_phases"]


def _requests(facts) -> Optional[dict]:
    if facts.get("t_start_ns") is None or not any(
            r["name"] == "request/queued"
            for r in facts.get("tracer_records", ())):
        return None
    chains = _chains(facts)
    if not chains:
        return None
    launches = {r["launch"]: r for r in _launches.rows(facts)}
    ends, packs = _holds(facts)
    kept, left = [], collections.Counter()
    for recs in chains.values():
        one = _phases([r for r in recs if r["ph"] == "X"], launches)
        if "left" in one:
            left[one["left"]] += 1
            continue
        # the ticks that packed a batch, start to end, while it sat in the
        # queue: one a tick, by the rule of its last pack
        lo = bisect.bisect_left(ends, one["t0"])
        hi = bisect.bisect_right(ends, one["t1"])
        one["holds"] = {tick: rule for _t1, t0, tick, rule, _n
                        in packs[lo:hi] if t0 >= one["t0"]}
        one["submit"] = recs[0].get("attrs") or {}
        kept.append(one)
    kept.sort(key=lambda x: x["t0"])
    w0, w1 = facts["t_start_ns"], facts["t_stop_ns"]
    return {"kept": kept, "left": left, "n": len(chains),
            "launches": launches,
            "depths": [p[4] for p in packs if w0 <= p[1] < w1]}


# ------------------------------------------------------------------ #
# the log
# ------------------------------------------------------------------ #
def _ms(values, q=None):
    if not values:
        return float("nan")
    return (sum(values) / len(values) if q is None
            else stats.pct(values, q)) / 1e6


def _three(values):
    return f"{_ms(values, 50):.3f} / {_ms(values, 90):.3f} / {_ms(values):.3f}"


def _log_harness(facts, kept, ctx):
    """The harness's own stamps of the same requests, matched by submit
    order: the track whose ``submitted`` is the last before the request's
    ``request/queued`` opened."""
    tracks = facts.get("tracks") or []
    subs = [int(t[2] * 1e9) for t in tracks]
    over, taken, prompts, tokens, ended = [], set(), 0, 0, 0
    for one in kept:
        i = bisect.bisect_right(subs, one["t0"]) - 1
        if i < 0 or i in taken or not tracks[i][3]:
            continue
        taken.add(i)
        prompt_len, _out, submitted, times = tracks[i]
        over.append(int((times[0] - submitted) * 1e9) - one["sum"])
        prompts += one["submit"].get("prompt_tokens") != prompt_len
        if "tokens" in one["end"]:
            ended += 1
            tokens += one["end"]["tokens"] + 1 != len(times)
    ctx.log(
        f"first token: the sum's p50 {_ms([x['sum'] for x in kept], 50):.3f}"
        f" ms beside the harness's ttft_ms p50 "
        f"{stats.pct(facts.get('ttft_ms') or [float('nan')], 50):.3f} and "
        f"gen_late_ms p50 "
        f"{stats.pct(facts.get('gen_late_ms') or [float('nan')], 50):.3f} "
        f"(the harness counts from when the request was due); matched by "
        f"submit order {len(over)} of {len(kept)}: the harness's first-token "
        f"stamp less its submit stamp exceeds the sum by p50 "
        f"{_ms(over, 50):.3f} / at most {_ms(over, 100):.3f}"
        f" ms; prompt_tokens differ in {prompts}; of {ended} whose decode "
        f"phase closed, tokens + 1 differs from the tokens the harness "
        f"counted in {tokens}")


def _log_device(facts, kept, ctx):
    execs, _info = _launches.joined(facts)
    off = _host_labels.offset_ns(facts)
    if not execs or off is None:
        ctx.log("first token: no traced stretch to check the host's "
                "estimate of behind / prefill against")
        return
    by_launch = {x["launch"]["launch"]: x for x in execs
                 if x["launch"] is not None and not x["cut"]}
    err_b, err_p = [], []
    for one in kept:
        a, b = by_launch.get(one["first"]["launch"]), \
            by_launch.get(one["last"]["launch"])
        if a is None or b is None:
            continue
        start = a["start"] - off                # on the ring's clock
        err_b.append(one["behind"] - (start - one["p0"]))
        err_p.append(one["prefill"] - (b["end"] - a["start"]))
    if not err_b:
        ctx.log("first token: no kept request's first and last launch were "
                "joined to whole executions of the traced stretch")
        return
    worst = lambda xs: max(xs, key=abs) / 1e6       # noqa: E731
    ctx.log(f"first token against the device: {len(err_b)} of {len(kept)} "
            f"kept requests had their first and last launch joined to whole "
            f"executions of the traced stretch; the host's estimate less the "
            f"executions' own start and end, mean / largest: behind "
            f"{_ms(err_b):.3f} / {worst(err_b):.3f} ms (holds the planes' "
            f"skew), prefill {_ms(err_p):.3f} / {worst(err_p):.3f} ms (the "
            f"wait returns after the execution ends)")


def _log(facts, got, ctx):
    kept, left = got["kept"], got["left"]
    ctx.log(f"first token: {got['n']} requests submitted in the window, "
            f"{len(kept)} kept; left out: "
            + (", ".join(f"{n} {why}" for why, n in sorted(left.items()))
               or "none"))
    if not kept:
        return
    ctx.log("first token, ms, p50 / p90 / mean: " + "; ".join(
        f"{ph} {_three([x[ph] for x in kept])}" for ph in PHASES)
        + f"; prompt chunks mean "
        f"{sum(x['chunks'] for x in kept) / len(kept):.2f}")
    _log_harness(facts, kept, ctx)
    rules = collections.Counter(r for x in kept for r in x["holds"].values())
    held = [x for x in kept if x["holds"]]
    ctx.log(f"first token: {sum(rules.values())} held ticks (a tick packed a "
            f"batch while the request sat in the queue) over {len(held)} of "
            f"{len(kept)} requests, by held_by: "
            + (", ".join(f"{r} {n}" for r, n in rules.most_common())
               or "none")
            + f"; queued p50 of the held "
            f"{_ms([x['queued'] for x in held], 50):.3f} ms, of the others "
            f"{_ms([x['queued'] for x in kept if not x['holds']], 50):.3f}; "
            f"{len(got['depths'])} packs of the window left someone waiting"
            + (f", {sum(got['depths']) / len(got['depths']):.1f} requests "
               f"on average and {max(got['depths'])} at most"
               if got["depths"] else ""))
    # by the program the first chunk was packed behind: a decode step (run
    # ahead), a ragged step, or none (the host and the device were level)
    groups: Dict[str, List[dict]] = collections.defaultdict(list)
    for x in kept:
        program = (got["launches"].get(x["behind_launch"]) or {}).get(
            "program") or "unknown"
        groups["level" if not x["behind_launch"] else
               program.split("_T")[0]].append(x)
    ctx.log("first token, by the program in flight when the first chunk was "
            "packed (behind_launch): " + "; ".join(
                f"{name} {len(xs)}: p50 ms " + ", ".join(
                    f"{ph} {_ms([x[ph] for x in xs], 50):.3f}"
                    for ph in PHASES + ("sum",))
                for name, xs in sorted(groups.items(),
                                       key=lambda kv: -len(kv[1]))))
    ends = collections.Counter(
        f"{x['end'].get('outcome', 'open')}/{x['end'].get('reason', '-')}"
        for x in kept)
    ctx.log("first token: the kept requests' last span in the ring closed "
            + ", ".join(f"{k} {n}" for k, n in ends.most_common()))
    _log_device(facts, kept, ctx)


def read(facts, args, ctx):
    got = requests(facts)
    if got is None:
        return None
    if "_request_phases_logged" not in facts:
        facts["_request_phases_logged"] = True
        _log(facts, got, ctx)
    kept = got["kept"]
    if not kept:
        return None
    what = args["what"]
    if what in PHASES:
        return stats.pct([x[what] for x in kept], float(args["q"])) / 1e6
    if what == "hold_ticks":
        return sum(len(x["holds"]) for x in kept) / len(kept)
    if what == "chunks":
        return sum(x["chunks"] for x in kept) / len(kept)
    raise ValueError(f"request_phase_ms: what={what!r}")
