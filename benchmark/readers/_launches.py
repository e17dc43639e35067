"""The launch record: which dispatch on the host is which execution on the
device.  Shared by ``launch_starved_ms_tick`` and ``launch_device_ms_tick``.

Since PR 33 the program numbers every step program it dispatches and names
it on both sides of its life: the dispatch span (``engine/decode_step``,
``engine/ragged_step``, ``engine/verify_step``) closes with ``launch`` and
``program``, and the wait that retires it (``fetch``,
``engine/fetch_logits``) closes with the same ``launch``.  A
program from before that records none of them: ``rows`` is then empty and
both readers return None.

Four things are built here, each once a run (kept in ``facts``):

* ``rows`` - one row a launch from the Tracer's ring, on
  ``time.monotonic_ns``, the whole run: number, program, the dispatching
  tick's span id and ``kind``, dispatch start / end, the end of the wait
  that retired it (the one that names it, or the first later wait: the
  device runs launches in order, and a prefill chunk that drains no
  sequence is waited for by nobody);
* ``starved`` - the intervals in which no launch was outstanding, from the
  end of the wait that retired the last outstanding launch to the end of
  the next dispatch span, each with the launch that ended it.  Host clock
  alone: no profiler, no skew;
* ``executions`` - one row per execution of a program on the first device:
  the events of the profile's "XLA Modules" line (``lib/xplane_modules``:
  one event an execution, named ``jit_<program>``), each with the device
  time of the operations inside it.  The stretch's first and last event
  are marked ``cut`` and left out of every number;
* ``join`` - executions to launches, by program name and order, anchored in
  time: walking the executions in device order, each takes the lowest
  launch number above the last one taken whose program is its own, whose
  dispatch opened before it started and whose retiring wait ended after it
  ended, both within ``TOL_NS``.  The tolerance is what lets the device
  plane run up to ~2 ms off the host plane (PERF.md section 7) without
  moving a number: no number below is a difference between the planes.
"""

from __future__ import annotations

import bisect
import collections
from typing import Dict, List, Optional, Tuple

from benchmark.lib import xplane_modules
from benchmark.readers import _host_labels

DISPATCH = ("engine/decode_step", "engine/ragged_step", "engine/verify_step")
WAIT = ("fetch", "engine/fetch_logits")
#: how far the device plane may stand off the host plane, either way, and a
#: launch still be joined to its execution: over the 0.6-1.9 ms measured,
#: under the shortest step program of any cell (7.9 ms)
TOL_NS = 3_000_000
BETWEEN = "between ticks"


# ------------------------------------------------------------------ #
# host: launches, ticks, starved intervals
# ------------------------------------------------------------------ #
def _tree(facts):
    """(spans by id, {span id: the ``tick`` record it descends from, or
    None}) of the ring's closed spans; a tick is its own."""
    if "_launch_tree" not in facts:
        spans = _host_labels.tracer_spans(facts)
        by_id = {r["span_id"]: r for r in spans}
        root: Dict[str, Optional[dict]] = {}
        for r in spans:
            chain, up = [], r
            while up is not None and up["name"] != "tick" \
                    and up["span_id"] not in root:
                chain.append(up["span_id"])
                up = by_id.get(up.get("parent"))
            top = None if up is None else \
                up if up["name"] == "tick" else root[up["span_id"]]
            for sid in chain:
                root[sid] = top
            if r["name"] == "tick":
                root[r["span_id"]] = r
        facts["_launch_tree"] = (by_id, root)
    return facts["_launch_tree"]


def _waits(facts) -> List[Tuple[int, int, int]]:
    """[(launch, start, end)] of the waits that name a launch, by number."""
    return sorted((int(r["attrs"]["launch"]), r["t0_ns"], r["t1_ns"])
                  for r in _host_labels.tracer_spans(facts)
                  if r["name"] in WAIT and "launch" in (r.get("attrs") or {}))


def rows(facts) -> List[dict]:
    """One row a launch, oldest first (see the module doc)."""
    if "_launch_rows" in facts:
        return facts["_launch_rows"]
    _, root = _tree(facts)
    waits = _waits(facts)
    wait_numbers = [w[0] for w in waits]
    out = []
    for r in _host_labels.tracer_spans(facts):
        a = r.get("attrs") or {}
        if r["name"] not in DISPATCH or "launch" not in a:
            continue
        tick = root.get(r["span_id"])
        i = bisect.bisect_left(wait_numbers, a["launch"])
        w = waits[i] if i < len(waits) else None
        out.append({
            "launch": int(a["launch"]), "program": a.get("program"),
            "d0": r["t0_ns"], "d1": r["t1_ns"],
            "r1": w[2] if w else None,
            "tick": tick["span_id"] if tick else None,
            "kind": (tick.get("attrs") or {}).get("kind") if tick else None})
    out.sort(key=lambda x: x["launch"])
    facts["_launch_rows"] = out
    return out


def starved(facts) -> List[Tuple[int, int, int]]:
    """[(start, end, launch)] on the host clock: no launch was outstanding
    from ``start`` (the end of the wait that retired the last one out) to
    ``end`` (the end of the dispatch span of ``launch``, the next one)."""
    if "_launch_starved" in facts:
        return facts["_launch_starved"]
    # (time, 0 = dispatched | 1 = retired, launch)
    events = sorted([(row["d1"], 0, row["launch"]) for row in rows(facts)]
                    + [(t1, 1, n) for n, _t0, t1 in _waits(facts)])
    out = []
    sent = done = 0                 # highest number dispatched / retired
    since = None                    # start of the open starved interval
    for t, what, n in events:
        if what == 0:
            if since is not None:
                out.append((since, t, n))
                since = None
            sent = max(sent, n)
        else:
            done = max(done, n)
            if done >= sent and since is None:
                since = t
    facts["_launch_starved"] = out
    return out


def split_by_span(facts, intervals) -> Dict[str, int]:
    """Nanoseconds of ``intervals`` [(start, end)] by the deepest Tracer
    span of a tick's tree that covers each instant (a span's self time:
    its own minus its children's), and ``BETWEEN`` for what no ``tick``
    span covers: the caller's loop."""
    by_id, root = _tree(facts)
    ticks = sorted((r for r in by_id.values() if r["name"] == "tick"),
                   key=lambda r: r["t0_ns"])
    starts = [t["t0_ns"] for t in ticks]
    under: Dict[str, List[dict]] = collections.defaultdict(list)
    for sid, top in root.items():
        if top is not None:
            under[top["span_id"]].append(by_id[sid])
    acc: Dict[str, int] = collections.defaultdict(int)
    for lo, hi in intervals:
        covered = 0
        i = max(bisect.bisect_right(starts, lo) - 1, 0)
        while i < len(ticks) and ticks[i]["t0_ns"] < hi:
            for r in under[ticks[i]["span_id"]]:
                ns = min(r["t1_ns"], hi) - max(r["t0_ns"], lo)
                if ns <= 0:
                    continue
                acc[r["name"]] += ns
                up = by_id.get(r.get("parent"))
                if up is not None and r["name"] != "tick":
                    acc[up["name"]] -= ns
                if r["name"] == "tick":
                    covered += ns
            i += 1
        acc[BETWEEN] += (hi - lo) - covered
    return {k: v for k, v in acc.items() if v}


# ------------------------------------------------------------------ #
# device: executions
# ------------------------------------------------------------------ #
def executions_of(modules, busy) -> List[dict]:
    """Executions of the first device, in device order, from its "XLA
    Modules" events ``modules`` [(device, start, end, program)]
    (``lib/xplane_modules``) and its busy intervals ``busy`` [(start,
    end)], disjoint and sorted (``TraceView.busy``): ``{"program",
    "start", "end", "busy", "cut"}``; ``busy`` = the device time of the
    operations inside the event.  ``cut`` marks the stretch's first and
    last event, which the capture may have opened or closed inside of (it
    closed inside chat's last decode step in PR 33's first call: 1.86 ms
    of 10.5): they are left out of every number."""
    if not modules:
        return []
    first = min(m[0] for m in modules)
    mine = sorted(m[1:] for m in modules if m[0] == first)
    ends = [b[1] for b in busy]
    out = []
    for k, (s, e, prog) in enumerate(mine):
        ns, i = 0, bisect.bisect_right(ends, s)
        while i < len(busy) and busy[i][0] < e:
            ns += min(busy[i][1], e) - max(busy[i][0], s)
            i += 1
        out.append({"program": prog, "start": s, "end": e, "busy": ns,
                    "cut": k in (0, len(mine) - 1)})
    return out


def executions(facts) -> List[dict]:
    if "_launch_executions" not in facts:
        path = (facts.get("capture") or {}).get("xplane")
        view = facts.get("view")
        mods = xplane_modules.device_modules(path) if path and view else []
        facts["_launch_executions"] = executions_of(
            mods, view.busy(min(m[0] for m in mods)) if mods else [])
    return facts["_launch_executions"]


# ------------------------------------------------------------------ #
# the join
# ------------------------------------------------------------------ #
def join_of(launches: List[dict], execs: List[dict], offset_ns: int,
            tol_ns: int = TOL_NS) -> Dict[str, object]:
    """Each execution's ``launch`` row (``execs[i]["launch"]``, None when
    no launch fits), in place; returns what the log says of the join:
    ``unjoined`` (whole executions no launch fits), ``missing`` (launch
    numbers between the first and the last joined that found no
    execution), ``skew_ns`` (the smallest shift of the device plane after
    which no joined execution starts before its own dispatch opened).
    ``offset_ns`` moves the rows' ``time.monotonic_ns`` onto the trace's
    clock."""
    numbers = [r["launch"] for r in launches]
    last = 0                                    # last launch number taken
    taken, unjoined = [], 0
    skew = 0
    for x in execs:
        x["launch"] = None
        i = bisect.bisect_right(numbers, last)
        while i < len(launches):
            row = launches[i]
            opened = row["d0"] + offset_ns
            if opened - tol_ns > x["start"]:
                break               # dispatched after it ran: no later fits
            if row["program"] == x["program"] and (
                    row["r1"] is None
                    or x["end"] <= row["r1"] + offset_ns + tol_ns):
                x["launch"] = row
                last = row["launch"]
                taken.append(last)
                if not x["cut"]:
                    skew = max(skew, opened - x["start"])
                break
            i += 1
        if x["launch"] is None and not x["cut"]:
            unjoined += 1
    missing = sorted(set(range(taken[0], taken[-1] + 1)) - set(taken)) \
        if taken else []
    return {"unjoined": unjoined, "missing": missing, "skew_ns": skew}


def joined(facts):
    """(executions with their ``launch`` rows and ``idle_before``, the
    join's log dict), or (None, None) without a trace, a clock anchor or a
    launch record.  Two executions that follow each other on the device
    with a launch number missing between them stand on either side of
    events the profile lost: both are marked ``cut`` and no gap is
    reckoned between them (``info["beside_lost"]`` counts them apart from
    ``info["ends"]``, the stretch's first and last).  ``info["longest"]``
    is the whole execution with the longest gap before it: the idle
    figures leave that one gap out (most traced stretches hold one stall
    of about 100 ms, PERF.md section 5, and a mean over 6 to 350 gaps
    would follow where it fell)."""
    if "_launch_joined" not in facts:
        off = _host_labels.offset_ns(facts)
        launches = rows(facts)
        execs = executions(facts) if off is not None and launches else []
        if not execs:
            facts["_launch_joined"] = (None, None)
        else:
            info = join_of(launches, execs, off)
            info["ends"] = sum(x["cut"] for x in execs)
            info["beside_lost"] = 0
            execs[0]["idle_before"] = None
            for prev, x in zip(execs, execs[1:]):
                x["idle_before"] = max(x["start"] - prev["end"], 0)
                if prev["launch"] and x["launch"] and \
                        x["launch"]["launch"] != prev["launch"]["launch"] + 1:
                    x["idle_before"] = None
                    info["beside_lost"] += (not prev["cut"]) + (not x["cut"])
                    prev["cut"] = x["cut"] = True
            info["longest"] = max(
                (x for x in execs if not x["cut"] and x["idle_before"]),
                key=lambda x: x["idle_before"], default=None)
            facts["_launch_joined"] = (execs, info)
    return facts["_launch_joined"]


def idle_gaps(execs, info) -> List[dict]:
    """The whole executions of ``execs`` whose gap before them counts in
    an idle figure: every one with a gap but ``info["longest"]``."""
    return [x for x in execs if not x["cut"] and x["idle_before"] is not None
            and x is not info["longest"]]
