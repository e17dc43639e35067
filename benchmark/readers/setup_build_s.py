"""Set-up by part, from the program's own records: the ``setup/*`` spans and
the ``setup/build_program`` record of every executable JAX built, as
``deepspeed_tpu.observability.tracer.process_tracer()`` holds them in the
run's own process (the readers run there, so no runner hands anything over).
Kept: the records that CLOSED before the window's first instant
(``facts["t_start_ns"]`` in a serving run; a training run's facts hold no such
stamp: there it is ``capture.mono_sync_ns - window_s``, the capture opening
right after the window; ``lower_train_step()`` builds again after it).

args: ``span`` (a name, or a list of names), ``where`` ({attr: value, or a
list of values}: the records whose attrs say so), and what to take of each:
``attr`` / ``sum_of`` (one attr, or the sum of several; seconds), neither:
the span's own length in seconds; ``count``: how many records, not seconds.

Once a run it also LOGS: the ten longest builds with their phases and what
the persistent cache said, the builds after the window's start by name and
by the span that caused them (the names behind ``programs_built_window``),
and the whole of ``process start -> window`` item by item as far as the
program's marks allow, each item with the build seconds inside it.  There
is one record a build, on the process tracer; the scheduler's tracer reads
the same clock, so the span that caused a build is the innermost of its
spans whose interval holds the record's end, and the launch that loaded a
program is the first dispatch record that names it.

A program without ``process_tracer`` (before PR 64) has no such record: the
reader returns None and the metric is left out.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Tuple

BUILD = "setup/build_program"
PHASES = ("trace_s", "lower_s", "backend_s")
STEP_PROGRAMS = ("ragged_step_", "decode_step", "verify_step_")
DISPATCHES = ("engine/ragged_step", "engine/decode_step",
              "engine/verify_step")


def window_start_ns(facts) -> Optional[int]:
    if facts.get("t_start_ns") is not None:
        return int(facts["t_start_ns"])
    cap = facts.get("capture") or {}
    if "mono_sync_ns" in cap and facts.get("window_s") is not None:
        return int(cap["mono_sync_ns"] - float(facts["window_s"]) * 1e9)
    return None


def process_records(facts) -> Optional[List[dict]]:
    """The process tracer's closed spans (``facts["process_records"]``
    where a test or a tool put them), ``setup/import`` among them even
    where the ring has gone round (``facts["process_dropped"]`` records
    fell out of it: the sums then lack the oldest builds)."""
    if "process_records" not in facts:
        try:
            from deepspeed_tpu.observability.tracer import process_tracer
        except ImportError:
            return None
        tr = process_tracer()
        recs = tr.records()
        if tr.import_span is not None and tr.import_span not in recs:
            recs.insert(0, tr.import_span)
        facts["process_records"] = recs
        facts["process_dropped"] = tr.dropped
    return [r for r in facts["process_records"] if r.get("ph") == "X"]


def _matches(rec: dict, names, where: Dict) -> bool:
    if rec["name"] not in names:
        return False
    attrs = rec.get("attrs") or {}
    return all(attrs.get(k) in (v if isinstance(v, list) else [v])
               for k, v in where.items())


def read(facts, args, ctx):
    recs, t_start = process_records(facts), window_start_ns(facts)
    if recs is None or t_start is None:
        return None
    if not facts.get("_setup_logged"):
        facts["_setup_logged"] = True
        _log_setup(facts, recs, t_start, ctx)
    names = args["span"] if isinstance(args["span"], list) else [args["span"]]
    if not any(r["name"] in names for r in recs):
        return None         # the program has no such span: nothing to read
    kept = [r for r in recs if r["t1_ns"] <= t_start
            and _matches(r, names, args.get("where", {}))]
    if args.get("count"):
        return len(kept)
    take = args.get("sum_of") or ([args["attr"]] if "attr" in args else None)
    if take is None:
        return sum(r["t1_ns"] - r["t0_ns"] for r in kept) / 1e9
    return sum((r.get("attrs") or {}).get(k, 0.0) for r in kept for k in take)


# ---------------------------------------------------------------------- #
# commentary
# ---------------------------------------------------------------------- #
def _phases(rec: dict) -> str:
    a = rec.get("attrs") or {}
    said = " + ".join(f"{k[:-2]} {a[k]:.2f}" for k in PHASES if k in a)
    hit = f", read in {a['retrieval_s']:.2f} s" if "retrieval_s" in a else ""
    return f"{a.get('program')} {said} s (cache {a.get('cache')}{hit})"


def _build_seconds(builds: List[dict], lo: int, hi: int) -> Dict[str, float]:
    """Seconds of the builds that closed in [lo, hi), by what they were:
    ``host`` (trace + lowering: no cache saves it), ``read`` (the backend's
    seconds where the persistent cache had the executable), ``compile``."""
    out = {"host": 0.0, "read": 0.0, "compile": 0.0, "n": 0}
    for r in builds:
        if lo <= r["t1_ns"] < hi:
            a = r["attrs"]
            out["n"] += 1
            out["host"] += a.get("trace_s", 0.0) + a.get("lower_s", 0.0)
            out["read" if a.get("cache") == "hit" else "compile"] += \
                a.get("backend_s", 0.0)
    return out


def _items(facts, recs, t_start: int, ctx) -> List[Tuple[str, int, int,
                                                       bool]]:
    """``process start -> window`` cut at the program's marks, in order:
    (what, from, to, whether it is a ``setup/*`` span of the program: the
    others are the gaps between the marks) on ``time.monotonic_ns``."""
    def first(name):
        return next((r for r in recs if r["name"] == name
                     and r["t1_ns"] <= t_start), None)

    imp = first("setup/import")
    if imp is None:
        return []
    t0 = int((imp.get("attrs") or {}).get("process_start_ns", imp["t0_ns"]))
    marks = [("process start -> setup/import: the interpreter, the harness's "
              "imports, jax, the device claim", t0, imp["t0_ns"], False),
             ("setup/import", imp["t0_ns"], imp["t1_ns"], True)]
    init, params = first("setup/engine_init"), first("setup/init_parameters")
    at = imp["t1_ns"]
    if init is not None:
        marks += [("setup/import -> setup/engine_init: " + (
                       "the weights" if facts.get("kind") == "serve"
                       else "the mesh"), at, init["t0_ns"], False),
                  ("setup/engine_init", init["t0_ns"], init["t1_ns"], True)]
        at = init["t1_ns"]
    if params is not None:
        marks += [("setup/engine_init -> setup/init_parameters: the first "
                   "batch", at, params["t0_ns"], False),
                  ("setup/init_parameters", params["t0_ns"],
                   params["t1_ns"], True)]
        at = params["t1_ns"]
    ticks = sorted((r for r in facts.get("tracer_records", ())
                    if r.get("ph") == "X" and r["name"] == "tick"
                    and r["t1_ns"] <= t_start), key=lambda r: r["t0_ns"])
    preroll = int(float(ctx.traffic.get("preroll_s", 0.0)) * 1e9) \
        if facts.get("kind") == "serve" else 0
    ladder = [r for r in ticks if r["t1_ns"] <= t_start - preroll]
    if ladder:
        marks += [("the engine -> the scheduler's first tick: the check "
                   "against the reference", at, ladder[0]["t0_ns"], False),
                  ("the shape ladder, first tick to last",
                   ladder[0]["t0_ns"], ladder[-1]["t1_ns"], False),
                  ("the last ladder tick -> the window: the pre-roll "
                   f"({preroll / 1e9:.1f} s fixed) and what lies before it",
                   ladder[-1]["t1_ns"], t_start, False)]
    else:
        marks.append((("the engine -> the window: the check, the shape "
                       "ladder, the pre-roll (no tick on a tracer)")
                      if facts.get("kind") == "serve" else
                      ("the parameters -> the window: the reference's loss, "
                       "the warm-up steps"), at, t_start, False))
    return marks


def _tick_spans(facts) -> List[dict]:
    """The closed spans of the scheduler's tracer, oldest first."""
    return sorted((r for r in facts.get("tracer_records", ())
                   if r.get("ph") == "X"), key=lambda r: r["t0_ns"])


def _first_launch_ticks(facts, t_lo: int, t_hi: int) -> Optional[str]:
    """The ladder's ticks, apart by whether one of their dispatches is the
    first record to name its ``program``: a tick that loads (or builds) an
    executable against one that only runs it.  (The first TRACED launch: a
    program the untraced check launched before is loaded already, and no
    build record closes inside such a tick.)"""
    spans = _tick_spans(facts)
    if not spans:
        return None
    parent = {r["span_id"]: r.get("parent") for r in spans}
    seen, firsts = set(), set()
    for r in spans:
        program = (r.get("attrs") or {}).get("program")
        if r["name"] in DISPATCHES and program not in seen:
            seen.add(program)
            up = r["span_id"]
            while parent.get(up) is not None:
                up = parent[up]
            firsts.add(up)
    ticks = [r for r in spans if r["name"] == "tick"
             and t_lo <= r["t0_ns"] and r["t1_ns"] <= t_hi]
    if not ticks:
        return None
    with_first = [r for r in ticks if r["span_id"] in firsts]
    s = lambda rs: sum(r["t1_ns"] - r["t0_ns"] for r in rs) / 1e9
    return (f"{len(with_first)} ticks with a program's first launch "
            f"{s(with_first):.2f} s, {len(ticks) - len(with_first)} others "
            f"{s(ticks) - s(with_first):.2f} s, between ticks "
            f"{(t_hi - t_lo) / 1e9 - s(ticks):.2f} s")


def _caused_by(spans: List[dict], t_ns: int) -> str:
    """The spans of the scheduler's tracer that were open at ``t_ns``,
    innermost first: ``engine/ragged_step <- prefill <- tick 7``."""
    holding = [r for r in spans if r["t0_ns"] <= t_ns <= r["t1_ns"]
               and not r["name"].startswith("request/")]
    if not holding:
        return ""
    by_id = {r["span_id"]: r for r in holding}
    inner = max(holding, key=lambda r: r["t0_ns"])
    chain, up = [], inner["span_id"]
    while up in by_id:
        r = by_id[up]
        chain.append(r["name"] + (f" {r['attrs']['tick']}"
                                  if r["name"] == "tick" else ""))
        up = r.get("parent")
    return " <- ".join(chain)


def _log_setup(facts, recs, t_start: int, ctx) -> None:
    log = ctx.log
    if facts.get("process_dropped"):
        log(f"set-up: {facts['process_dropped']} records fell out of the "
            f"process tracer's ring: the sums below lack the oldest builds")
    builds = [r for r in recs if r["name"] == BUILD]
    before = [r for r in builds if r["t1_ns"] <= t_start]
    total = _build_seconds(before, 0, t_start)
    log(f"set-up: {len(before)} executables built before the window: trace "
        f"+ lowering {total['host']:.2f} s, read from the cache "
        f"{total['read']:.2f} s, compiled {total['compile']:.2f} s; the "
        f"longest: " + "; ".join(_phases(r) for r in sorted(
            before, key=lambda r: r["t0_ns"] - r["t1_ns"])[:10]))
    steps = sorted({r["attrs"]["program"] for r in before
                    if r["attrs"].get("cache") != "hit"
                    and r["attrs"]["program"].startswith(STEP_PROGRAMS)})
    log(f"set-up: step programs the persistent cache did not hold: "
        f"{steps or 'none'}")

    # -- the builds after the window's start, by name and by cause ------ #
    after = [r for r in builds if r["t1_ns"] > t_start]
    t_stop = facts.get("t_stop_ns") or (
        t_start + int(float(facts.get("window_s", 0.0)) * 1e9))
    spans = _tick_spans(facts)
    inside = [r for r in after if r["t1_ns"] <= t_stop]
    caused = {r["span_id"]: _caused_by(spans, r["t1_ns"]) for r in inside}
    log(f"set-up: {len(inside)} executable(s) built INSIDE the window"
        + (": " + "; ".join(
            _phases(r) + (f" under {caused[r['span_id']]}"
                          if caused[r["span_id"]] else "")
            for r in inside) if inside else "")
        + f"; {len(after) - len(inside)} after it"
        + (" (the harness's own lowering and memory analysis): "
           + ", ".join(sorted({r["attrs"]["program"] for r in after
                               if r["t1_ns"] > t_stop}))
           if len(after) > len(inside) else ""))

    # -- process start -> window, item by item -------------------------- #
    items = _items(facts, recs, t_start, ctx)
    if not items:
        return
    t0 = items[0][1]
    printed = _printed_setup_s(t_start)
    log(f"set-up: process start -> the window's first instant "
        f"{(t_start - t0) / 1e9:.2f} s by the program's own marks "
        f"(process_start_ns: the kernel's account of the process)" + (
            f"; the run's setup_s {printed:.2f} s, "
            f"{100.0 * ((t_start - t0) / 1e9 / printed - 1):+.2f}% apart"
            if printed else ""))
    for what, lo, hi, _is_span in items:
        b = _build_seconds(before, lo, hi)
        line = f"set-up:   {(hi - lo) / 1e9:7.2f} s  {what}"
        if b["n"]:
            line += (f" [{b['n']} builds: trace + lowering {b['host']:.2f}, "
                     f"cache read {b['read']:.2f}, compile "
                     f"{b['compile']:.2f}]")
        if what.startswith("the shape ladder"):
            split = _first_launch_ticks(facts, lo, hi)
            if split:
                line += f" ({split})"
        log(line)
    own = [(lo, hi) for _what, lo, hi, is_span in items if is_span]
    named = sum(hi - lo for lo, hi in own)
    spans_and_builds = _covered(
        own + [(r["t0_ns"], r["t1_ns"]) for r in before], t0, t_start)
    log(f"set-up: the setup/* spans hold {named / 1e9:.2f} s, spans and "
        f"build records together cover {spans_and_builds / 1e9:.2f} s = "
        f"{100.0 * spans_and_builds / max(t_start - t0, 1):.1f}% of it; the "
        f"rest is the harness's, item by item above")


def _printed_setup_s(t_start: int) -> Optional[float]:
    """``setup_s`` as ``benchmark/run.py`` will print it: the window's
    start against the clock it read at its own top."""
    import sys

    for name in ("benchmark.run", "__main__"):
        began = getattr(sys.modules.get(name), "_T_PROCESS_START", None)
        if began is not None:
            return t_start / 1e9 - began
    return None


def _covered(intervals: List[Tuple[int, int]], lo: int, hi: int) -> int:
    """Nanoseconds of [lo, hi) that some interval covers."""
    total, at = 0, lo
    for a, b in sorted(intervals):
        a, b = max(a, at), min(b, hi)
        if b > a:
            total += b - a
            at = b
    return total
