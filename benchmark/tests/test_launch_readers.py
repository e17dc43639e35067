"""The two readers over the launch record (PR 33), on hand-made Tracer
records, module events and device operations: two synchronous mixed ticks, the first decode
tick after them (which dispatches its own step and the next), a run of
decode ticks ahead, a prefill chunk nobody waits for, and a stretch that
cuts an execution at either end.  Then a CPU rehearsal in which a tiny
serving cell reports the three ``program_span`` metrics."""

import pytest

from benchmark.lib import xplane_modules
from benchmark.lib.tracing import HostEvent, TraceView
from benchmark.readers import (_launches, launch_device_ms_tick,
                               launch_starved_ms_tick)

MS = 1_000_000
US = 1_000
#: profiler clock = time.monotonic_ns + OFF
OFF = 1_000 * MS
T288, T544, DEC = ("ragged_step_T288_tiled", "ragged_step_T544_tiled",
                   "decode_step")


class Ctx:
    def __init__(self):
        self.lines = []
        self.peaks = None

    def log(self, msg):
        self.lines.append(msg)


def _span(sid, name, t0, t1, parent=None, **attrs):
    rec = {"name": name, "ph": "X", "tid": "main", "trace_id": "t",
           "span_id": sid, "parent": parent, "t0_ns": int(t0 * MS),
           "t1_ns": int(t1 * MS)}
    if attrs:
        rec["attrs"] = attrs
    return rec


def _dispatch(sid, name, t0, t1, parent, n, program):
    return _span(sid, name, t0, t1, parent, launch=n, program=program)


def _mixed(tk, t0, n, program=T288):
    """A synchronous mixed tick of 50 ms from ``t0``: pack 2, build 3,
    dispatch 1, the wait 40, sample 3 (its advance 1), 1 of the tick's
    own."""
    p = tk + "p"
    return [
        _span(tk, "tick", t0, t0 + 50, kind="mixed", emitted=9),
        _span(tk + "k", "pack", t0, t0 + 2, tk),
        _span(p, "prefill", t0 + 2, t0 + 46, tk),
        _span(tk + "a", "engine/build_batch", t0 + 2, t0 + 5, p,
              tokens=200, bucket=288),
        _dispatch(tk + "b", "engine/ragged_step", t0 + 5, t0 + 6, p, n,
                  program),
        _span(tk + "c", "engine/fetch_logits", t0 + 6, t0 + 46, p, launch=n),
        _span(tk + "s", "sample", t0 + 46, t0 + 49, tk),
        _span(tk + "d", "advance", t0 + 48, t0 + 49, tk + "s")]


def _records():
    """Window 0 .. 200 ms.  Launches: 1, 2 = two mixed ticks (0, 51); 3, 4 =
    the decode tick after them (101: its own step and the one after it); 5,
    6 = the ticks at 113 and 124, each a step ahead; the tick at 135 only
    returns 6; 7 = a prefill chunk that drains no sequence (147: nobody
    waits for it); 8 = a mixed tick (152) dispatched while 7 is out; 9 = a
    decode tick past the window."""
    r = _mixed("m1", 0, 1) + _mixed("m2", 51, 2)
    # the first decode tick after a mixed one
    r += [_span("d1", "tick", 101, 113, kind="decode", emitted=4),
          _span("d1k", "pack", 101, 101.1, "d1"),
          _span("d1p", "decode", 101.1, 113, "d1", ahead=0, steps=1),
          _span("d1a", "engine/decode_prep", 101.1, 101.5, "d1p", seqs=4),
          _dispatch("d1b", "engine/decode_step", 101.5, 102, "d1p", 3, DEC),
          _span("d1e", "engine/decode_prep", 102, 102.2, "d1p", seqs=4),
          _dispatch("d1f", "engine/decode_step", 102.2, 102.7, "d1p", 4,
                    DEC),
          _span("d1c", "fetch", 102.7, 112, "d1p", launch=3),
          _span("d1d", "advance", 112, 113, "d1p")]
    # a run of decode ticks ahead, and the tick that ends it
    for i, (t0, sent, back) in enumerate(((113, 5, 4), (124, 6, 5),
                                          (135, None, 6)), start=2):
        tk, p = f"d{i}", f"d{i}p"
        r += [_span(tk, "tick", t0, t0 + 11, kind="decode", emitted=4),
              _span(tk + "k", "pack", t0, t0 + .1, tk),
              _span(p, "decode", t0 + .1, t0 + 11, tk, ahead=1, steps=1),
              _span(tk + "c", "fetch", t0 + 1, t0 + 10, p, launch=back),
              _span(tk + "d", "advance", t0 + 10, t0 + 11, p)]
        if sent:
            r += [_span(tk + "a", "engine/decode_prep", t0 + .2, t0 + .4, p,
                        seqs=4),
                  _dispatch(tk + "b", "engine/decode_step", t0 + .4, t0 + 1,
                            p, sent, DEC)]
    r += [_span("p1", "tick", 147, 151, kind="prefill", emitted=0),
          _span("p1p", "prefill", 147, 150.5, "p1"),
          _span("p1a", "engine/build_batch", 147, 149, "p1p", tokens=512,
                bucket=544),
          _dispatch("p1b", "engine/ragged_step", 149, 150, "p1p", 7, T544),
          _span("m3", "tick", 152, 199, kind="mixed", emitted=5),
          _span("m3k", "pack", 152, 153, "m3"),
          _span("m3p", "prefill", 153, 198, "m3"),
          _span("m3a", "engine/build_batch", 153, 155, "m3p", tokens=200,
                bucket=288),
          _dispatch("m3b", "engine/ragged_step", 155, 156, "m3p", 8, T288),
          _span("m3c", "engine/fetch_logits", 156, 198, "m3p", launch=8),
          _span("m3s", "sample", 198, 199, "m3")]
    # past the window: its starved time is nobody's in the window
    r += [_span("d5", "tick", 199.5, 212, kind="decode"),
          _span("d5p", "decode", 199.6, 212, "d5"),
          _dispatch("d5b", "engine/decode_step", 200, 200.5, "d5p", 9, DEC),
          _span("d5c", "fetch", 200.5, 211, "d5p", launch=9),
          _span("req", "request/decode", 0, 150)]
    return r


#: executions on the device, on the host's clock (ms): launch, program,
#: start, end.  Between 0.05 (a step ahead) and 0.4 ms after the dispatch
#: ended or the execution before it did
_RUNS = [(1, T288, 6.2, 45.8), (2, T288, 57.4, 96.8), (3, DEC, 102.1, 111.9),
         (4, DEC, 111.95, 122.9), (5, DEC, 122.95, 133.9),
         (6, DEC, 133.95, 144.9), (7, T544, 150.3, 160.0),
         (8, T288, 160.05, 197.8)]
#: every execution pauses this long between its two operations
PAUSE = 0.02


def _trace(shift_ms=0.0, drop=(), lo=30.0, hi=170.0):
    """(module events, busy intervals) of ``_RUNS`` on the profiler's
    clock, the device plane ``shift_ms`` off the host plane, as a stretch
    ``lo .. hi`` saw them: an execution an end of the stretch cut shows as
    a shorter event.  An execution is two operations with ``PAUSE``
    between them."""
    mods, busy = [], []
    for n, prog, s, e in _RUNS:
        if n in drop or e <= lo or s >= hi:
            continue
        at = lambda ms: int((ms + shift_ms) * MS) + OFF     # noqa: E731
        mods.append((0, at(max(s, lo)), at(min(e, hi)), prog))
        mid = (s + e) / 2
        for a, b in ((s, mid - PAUSE / 2), (mid + PAUSE / 2, e)):
            if a < hi and b > lo:
                busy.append((at(max(a, lo)), at(min(b, hi))))
    return mods, busy


def _facts(trace=None, **more):
    facts = {"tracer_records": _records(), "t_start_ns": 0,
             "t_stop_ns": 200 * MS, **more}
    if trace is not None:
        facts["view"] = TraceView([], [HostEvent(
            "main", "bench/clock_sync", 7 * MS + OFF, 10 * US)])
        facts["capture"] = {"mono_sync_ns": 7 * MS}
        facts["_launch_executions"] = _launches.executions_of(*trace)
    return facts


# ------------------------------------------------------------------ #
# the host's side: rows, starved intervals, their split
# ------------------------------------------------------------------ #
def test_one_row_a_launch():
    rows = _launches.rows(_facts())
    assert [r["launch"] for r in rows] == list(range(1, 10))
    assert [r["program"] for r in rows] == \
        [T288, T288, DEC, DEC, DEC, DEC, T544, T288, DEC]
    assert [r["kind"] for r in rows] == \
        ["mixed", "mixed", "decode", "decode", "decode", "decode",
         "prefill", "mixed", "decode"]
    assert [r["tick"] for r in rows] == \
        ["m1", "m2", "d1", "d1", "d2", "d3", "p1", "m3", "d5"]
    assert [(r["d0"], r["d1"]) for r in rows[:3]] == \
        [(5 * MS, 6 * MS), (56 * MS, 57 * MS),
         (int(101.5 * MS), 102 * MS)]
    # the wait that names it; 7 is retired by the wait for 8
    assert [r["r1"] for r in rows] == [
        46 * MS, 97 * MS, 112 * MS, 123 * MS, 134 * MS, 145 * MS, 198 * MS,
        198 * MS, 211 * MS]


def test_starved_intervals():
    """No launch outstanding: from the end of the wait that retired the
    last one to the end of the next dispatch span.  A step ahead leaves
    none; the chunk nobody waits for keeps the device supplied until the
    wait for the launch after it."""
    assert _launches.starved(_facts()) == [
        (46 * MS, 57 * MS, 2), (97 * MS, 102 * MS, 3),
        (145 * MS, 150 * MS, 7), (198 * MS, int(200.5 * MS), 9)]


@pytest.mark.parametrize("kind, want", [
    # m2 11 (m1's sample 3 with its advance, 1 of m1's own, 1 between the
    # ticks, pack 2, build 3, dispatch 1) + p1 5 + m1, m3 0, over 4 ticks
    ("mixed+prefill", (11 + 5) / 4),
    ("mixed", 11 / 3),
    ("prefill", 5.0),
    # d1 alone: m2's sample and own time 4, its pack .1, prep .4, dispatch .5
    ("decode", 5 / 4),
])
def test_starved_ms_tick(kind, want):
    ctx = Ctx()
    assert launch_starved_ms_tick.read(_facts(), {"kind": kind}, ctx) == \
        pytest.approx(want)
    assert len(ctx.lines) == 1


def test_a_synchronous_mixed_tick_is_starved_for_its_serial_part():
    """m2, after m1: exactly pack + build + dispatch + sample + advance
    (+ what the tick and the caller's loop spend outside any phase), each
    under its own name by self time."""
    facts = _facts()
    (s, e, n), = [x for x in _launches.starved(facts) if x[2] == 2]
    split = _launches.split_by_span(facts, [(s, e)])
    assert split == {"sample": 2 * MS, "advance": 1 * MS, "tick": 1 * MS,
                     _launches.BETWEEN: 1 * MS, "pack": 2 * MS,
                     "engine/build_batch": 3 * MS,
                     "engine/ragged_step": 1 * MS}
    assert sum(split.values()) == e - s == (2 + 3 + 1 + 3) * MS + 2 * MS


def test_the_first_decode_tick_after_a_mixed_one_is_charged_its_dispatch():
    facts = _facts()
    (s, e, n), = [x for x in _launches.starved(facts) if x[2] == 3]
    split = _launches.split_by_span(facts, [(s, e)])
    assert {k: v for k, v in split.items()
            if k in ("pack", "engine/decode_prep", "engine/decode_step")} == \
        {"pack": MS // 10, "engine/decode_prep": 4 * MS // 10,
         "engine/decode_step": 5 * MS // 10}
    assert sum(split.values()) == 5 * MS
    # the run of decode ticks ahead behind it is starved 0
    by_tick = {r["launch"]: r["tick"] for r in _launches.rows(facts)}
    assert not [x for x in _launches.starved(facts)
                if by_tick[x[2]] in ("d2", "d3", "d4")]


def test_the_log_names_the_split_and_the_idle_launches():
    ctx = Ctx()
    launch_starved_ms_tick.read(_facts(), {"kind": "mixed+prefill"}, ctx)
    line, = ctx.lines
    assert "4 mixed+prefill ticks in the window made 4 launches; " in line
    assert "starved 4.000 ms a tick over 2 intervals" in line
    # m2's 3 + p1's 2 (147 .. 149) of build over four ticks; between ticks:
    # 50 .. 51 and 146 .. 147
    assert "engine/build_batch 1.250" in line and "sample 0.500" in line
    assert f"{_launches.BETWEEN} 0.500" in line
    ctx = Ctx()
    launch_starved_ms_tick.read(_facts(), {"kind": "decode"}, ctx)
    assert "4 decode ticks in the window made 4 launches; " in ctx.lines[0]


def test_a_program_without_the_launch_record_reports_nothing():
    old = []
    for r in _records():
        a = {k: v for k, v in (r.get("attrs") or {}).items()
             if k not in ("launch", "program")}
        old.append({**{k: v for k, v in r.items() if k != "attrs"},
                    **({"attrs": a} if a else {})})
    facts = dict(_facts(_trace()), tracer_records=old)
    ctx = Ctx()
    assert launch_starved_ms_tick.read(facts, {"kind": "decode"}, ctx) is None
    assert launch_device_ms_tick.read(
        facts, {"kind": "decode", "what": "busy"}, ctx) is None
    assert ctx.lines == []


# ------------------------------------------------------------------ #
# the device's side: executions, the join, the numbers
# ------------------------------------------------------------------ #
@pytest.mark.parametrize("name, program", [
    ("jit_decode_step(7246663385873248887)", DEC),
    ("jit_ragged_step_T288_tiled(1)", T288),
    ("jit_verify_step_K4", "verify_step_K4"),
    ("SyncTensorsGraph.12", "SyncTensorsGraph.12")])
def test_a_module_event_names_its_program(name, program):
    assert xplane_modules.program_of(name) == program


def test_executions_are_the_module_events():
    execs = _launches.executions_of(*_trace())
    assert [x["program"] for x in execs] == \
        [T288, T288, DEC, DEC, DEC, DEC, T544, T288]
    # the stretch opened inside launch 1's execution and closed inside
    # launch 8's: the first and the last are marked, none between them
    assert [x["cut"] for x in execs] == [True] + [False] * 6 + [True]
    x = execs[2]
    assert (x["start"], x["end"]) == (int(102.1 * MS) + OFF,
                                      int(111.9 * MS) + OFF)
    # the device time of the operations inside the event, not its length
    assert x["busy"] == pytest.approx((9.8 - PAUSE) * MS, abs=8)
    # another device's events are not the first device's
    mods, busy = _trace()
    assert _launches.executions_of(
        mods + [(1, s, e, p) for _, s, e, p in mods], busy) == execs
    assert _launches.executions_of([], []) == []


def test_a_hole_in_the_profile_leaves_no_whole_execution_beside_it():
    """The profile lost the device's events from inside launch 4's
    execution to inside launch 6's (seen on the chip once: 2.6 s of a
    3.9 s stretch): launch 5 finds no execution and is named as missing,
    the executions on either side of it are left out like the stretch's
    ends, counted apart from them, and no idle time is reckoned between
    them."""
    mods, busy = _trace(drop=(5,))
    lost = (int(115 * MS) + OFF, int(136 * MS) + OFF)
    busy = [(s, min(e, lost[0])) if s < lost[0] < e else (s, e)
            for s, e in busy if not lost[0] <= s < lost[1]]
    facts = _facts((mods, busy))
    execs, info = _launches.joined(facts)
    assert [(x["launch"]["launch"], x["cut"]) for x in execs] == [
        (1, True), (2, False), (3, False), (4, True), (6, True),
        (7, False), (8, True)]
    assert info["ends"] == 2 and info["beside_lost"] == 2
    assert info["missing"] == [5]
    assert [x["idle_before"] is None for x in execs] == \
        [True, False, False, False, True, False, False]
    ctx = Ctx()
    assert launch_device_ms_tick.read(
        facts, {"kind": "decode", "what": "busy"}, ctx) == \
        pytest.approx(9.8 - PAUSE)                  # launch 3 alone
    assert any("2 execution(s) left out at the stretch's ends and 2 beside "
               "launches the profile lost, 1 launch(es) of the stretch "
               "without an execution [5]" in l for l in ctx.lines)


@pytest.mark.parametrize("shift", [-1.9, 0.0, 1.9])
def test_the_join_and_the_six_numbers_do_not_move_with_the_skew(shift):
    ctx = Ctx()
    facts = _facts(_trace(shift))
    execs, info = _launches.joined(facts)
    assert [x["launch"]["launch"] for x in execs] == list(range(1, 9))
    assert info["missing"] == [] and info["unjoined"] == 0
    assert info["ends"] == 2 and info["beside_lost"] == 0
    # the longest gap, before launch 2 (57.4 - 45.8), is in no idle figure
    assert info["longest"]["launch"]["launch"] == 2
    got = {(kind, what): launch_device_ms_tick.read(
        facts, {"kind": kind, "what": what}, ctx)
        for kind in ("mixed+prefill", "decode", "prefill")
        for what in ("busy", "idle")}
    assert got == {
        # launches 2 (T288) and 7 (T544); 1 and 8 are cut
        ("mixed+prefill", "busy"): pytest.approx(
            (39.4 + 9.7) / 2 - PAUSE, abs=1e-4),
        ("mixed+prefill", "idle"): pytest.approx(150.3 - 144.9, abs=1e-4),
        ("decode", "busy"): pytest.approx(
            (9.8 + 3 * 10.95) / 4 - PAUSE, abs=1e-4),
        ("decode", "idle"): pytest.approx((102.1 - 96.8 + 3 * .05) / 4,
                                          abs=1e-4),
        ("prefill", "busy"): pytest.approx(9.7 - PAUSE, abs=1e-4),
        ("prefill", "idle"): pytest.approx(150.3 - 144.9, abs=1e-4)}
    # what the join saw of the skew: launch 3's dispatch opened at 101.5,
    # its execution started at 102.1 + shift
    assert info["skew_ns"] == pytest.approx(
        max(0, (101.5 - 102.1 - shift) * MS), abs=2)
    table = next(l for l in ctx.lines if "program -> whole executions" in l)
    assert f"{DEC} 4, 10.64" in table and "(9.78 - 10.93), 1.36" in table \
        and f"{T288} 1, 39.380 (39.38 - 39.38), nan" in table \
        and f"{T544} 1, 9.680 (9.68 - 9.68), 5.400" in table
    ends = next(l for l in ctx.lines if "execution(s) left out" in l)
    assert ends.startswith("launches: 2 execution(s) left out at the "
                           "stretch's ends and 0 beside launches")
    assert "0 launch(es) of the stretch without an execution" in ends
    assert "0 whole execution(s) without a launch" in ends
    # the six whole executions' pauses, and the seven gaps: 11.6 + 5.3 +
    # 3 x .05 + 5.4 + .05
    assert f"of {(22.5 + 6 * PAUSE) / 1e3:.4f} s idle in a stretch of " \
        f"0.1400 s, 0.0225 s between executions and " \
        f"{6 * PAUSE / 1e3:.4f} s inside them" in ends
    worst = next(l for l in ctx.lines if "the longest gap" in l)
    assert "11.600 ms before launch 2 (ragged_step_T288_tiled, a mixed " \
        "tick), is left out of every idle figure: the mean over all 6 " \
        "gaps is 3.742 ms with it, 2.170 without" in worst


def test_idle_is_set_beside_the_starved_time_of_the_same_launches():
    ctx = Ctx()
    facts = _facts(_trace())
    idle = launch_device_ms_tick.read(
        facts, {"kind": "decode", "what": "idle"}, ctx)
    line = next(l for l in ctx.lines if "launches of decode" in l)
    # launches 3 .. 6: the device waited for 3 (starved 5 ms), not for the
    # three steps sent ahead
    assert "4 whole executions in the stretch, the device waited more " \
        "than 0.1 ms for 25.0% of them" in line
    assert f"idle before one {idle:.3f} ms" in line
    assert "the host starved the device 1.250 ms" in line
    assert f"{idle - 1.25:.3f} ms are not explained by the host" in line
    # launch 3: the wait returned 97 - 96.8 after launch 2's execution
    # ended, its own started 102.1 - 102 after its dispatch span closed
    assert "returned 0.050 ms after the execution before it ended" in line
    assert "started 0.025 ms after its dispatch span closed" in line
    assert idle == pytest.approx((5 + .2 + .1 + 3 * .05) / 4)


def test_an_unjoined_launch_is_logged_not_skipped():
    """Launch 5's execution is not in the trace (a reader that cut two
    executions as one would look the same): the launch is named, the
    executions behind it keep their own launches."""
    ctx = Ctx()
    facts = _facts(_trace(drop=(5,)))
    execs, info = _launches.joined(facts)
    assert [x["launch"]["launch"] for x in execs] == [1, 2, 3, 4, 6, 7, 8]
    assert info["missing"] == [5]
    launch_device_ms_tick.read(facts, {"kind": "decode", "what": "busy"}, ctx)
    assert any("1 launch(es) of the stretch without an execution [5]" in l
               for l in ctx.lines)


def test_an_execution_no_launch_fits_is_counted():
    """The host recorded another program for launch 7 than the device ran:
    the execution takes no launch (the next one of its name does not
    exist), the launch finds no execution; both are counted, and the
    execution behind them keeps its own launch."""
    facts = _facts(_trace())
    for r in facts["tracer_records"]:
        if (r.get("attrs") or {}).get("program") == T544:
            r["attrs"]["program"] = "ragged_step_T1056_tiled"
    execs, info = _launches.joined(facts)
    assert [x["launch"] and x["launch"]["launch"] for x in execs] == \
        [1, 2, 3, 4, 5, 6, None, 8]
    assert info["unjoined"] == 1 and info["missing"] == [7]


# ------------------------------------------------------------------ #
# rehearsal: a tiny serving cell reports the program_span metrics
# ------------------------------------------------------------------ #
@pytest.mark.parametrize("cell, want", [
    ("serve-mistral7b-chat-steady", {"chat_starved_ms_decode_tick"}),
    ("serve-mistral7b-longprompt-closed", {"starved_ms_mixed_tick",
                                           "starved_ms_decode_tick"})])
def test_rehearsal_reports_the_starved_metrics(cell, want):
    from benchmark import run
    from benchmark.tests.rehearsal_sizes import TINY

    out = run.run_cell(cell, 5, 2.0, True, overrides=TINY[cell],
                       allow_cpu=True)
    facts = out.pop("_facts")
    assert out["rehearsal"] and out["correct"] is True
    assert want <= set(out["metrics"])
    assert all(out["metrics"][m]["value"] >= 0 for m in want)
    # nothing ran on a device: the six device numbers are left out
    assert not [m for m in out["metrics"]
                if m.endswith(("_idle_ms_tick", "_exec_ms_tick"))]
    rows = _launches.rows(facts)
    assert rows and [r["launch"] for r in rows] == \
        list(range(rows[0]["launch"], rows[0]["launch"] + len(rows)))
    assert {r["program"].split("_T")[0] for r in rows} <= \
        {"decode_step", "ragged_step"}
    # a starved interval lies between the wait before it and its dispatch
    by_n = {r["launch"]: r for r in rows}
    for s, e, n in _launches.starved(facts):
        assert s < e == by_n[n]["d1"]
