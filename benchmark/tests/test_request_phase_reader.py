"""The reader of a request's first token (PR 48) on a hand-made ring: a
request that finds the device level, one held a tick by the budget that
arrives behind a decode step and takes two chunks, one whose first launch
is dispatched while the launch before it still runs, one that ends with its
first token; and those left out: preempted before the first token, still in
the queue, a launch the ring does not hold, submitted before the window.
Then the same numbers against executions on a device plane level with the
host's, and a CPU rehearsal of both kinds of serving cell."""

import pytest

from benchmark.lib.tracing import HostEvent, TraceView
from benchmark.readers import _launches, request_phase_ms

MS = 1_000_000
US = 1_000
OFF = 1_000 * MS
T288, DEC = "ragged_step_T288_tiled", "decode_step"


class Ctx:
    def __init__(self):
        self.lines = []
        self.peaks = None

    def log(self, msg):
        self.lines.append(msg)


def _span(sid, name, t0, t1, parent=None, trace="t", **attrs):
    rec = {"name": name, "ph": "X", "tid": "main", "trace_id": trace,
           "span_id": sid, "parent": parent, "t0_ns": int(t0 * MS),
           "t1_ns": int(t1 * MS)}
    if attrs:
        rec["attrs"] = attrs
    return rec


def _launch(n, program, d0, d1, w0=None, w1=None):
    """Dispatch span of launch ``n`` and, with ``w0``, the wait that names
    it."""
    name = "engine/decode_step" if program == DEC else "engine/ragged_step"
    out = [_span(f"l{n}", name, d0, d1, launch=n, program=program)]
    if w0 is not None:
        out.append(_span(f"w{n}", "fetch", w0, w1, launch=n))
    return out


def _request(trace, submit, admit, first_token=None, end=None, prompt=64,
             **prefill):
    """``request/submit`` and the chain under ``trace``: queued ``submit``
    -> ``admit`` (2 us before the prefill span opens), prefill -> the first
    token, decode -> ``end``."""
    recs = [{"name": "request/submit", "ph": "i", "tid": "main",
             "trace_id": trace, "span_id": trace + "s", "parent": None,
             "t0_ns": int(submit * MS) - US,
             "attrs": {"uid": 1, "prompt_tokens": prompt}}]
    if admit is None:
        return recs                             # still in the queue
    recs.append(_span(trace + "q", "request/queued", submit,
                      admit - 0.002, trace=trace))
    if first_token is None:
        return recs
    recs.append(_span(trace + "p", "request/prefill", admit, first_token,
                      trace=trace, **prefill))
    if end is not None:
        recs.append(_span(trace + "d", "request/decode", first_token, end,
                          trace=trace, tokens=5, outcome="finished",
                          reason="length"))
    return recs


def _records():
    """Window 0 .. 200 ms."""
    r = []
    # A: level.  Launch 1 dispatched 14-15, waited for 15-38, token at 40
    r += _launch(1, T288, 14, 15, 15, 38)
    r += _request("A", 10, 12, 40, 90, chunks=1, first_launch=1,
                  last_launch=1, behind_launch=0)
    # B: held a tick by the budget (pack 55-56), admitted behind decode
    # step 2 (retired at 66); chunks in launches 3 and 4
    r += [_span("t5", "tick", 54, 58, kind="mixed"),
          _span("t5k", "pack", 55, 56, "t5", queued=1, held_by="budget"),
          # (a second pack of the same tick: counted once)
          _span("t5k2", "pack", 56.5, 57, "t5", queued=1),
          _span("t6", "tick", 60, 70, kind="mixed"),
          _span("t6k", "pack", 61, 63, "t6", queued=0)]
    r += _launch(2, DEC, 45, 46, 64, 66)
    r += _launch(3, T288, 66.5, 67, 78, 79)
    r += _launch(4, T288, 80, 81, 90, 95)
    r += _request("B", 50, 62, 97, 180, prompt=500, chunks=2, first_launch=3,
                  last_launch=4, behind_launch=2)
    # C: launch 6 is dispatched (120-121) while launch 5 still runs (its
    # wait ends at 125): it starts when 5 ends
    r += _launch(5, DEC, 105, 106, 112, 125)
    r += _launch(6, T288, 120, 121, 126, 150)
    r += _request("C", 110, 111, 151, None, chunks=1, first_launch=6,
                  last_launch=6, behind_launch=5)
    # H: ends with its first token (no decode phase): launch 7
    r += _launch(7, T288, 162, 163, 163, 170)
    r += _request("H", 160, 161, 171, None, chunks=1, first_launch=7,
                  last_launch=7, behind_launch=0, outcome="finished",
                  reason="length")
    # left out: D preempted before its first token; F still in the queue;
    # G names a launch the ring does not hold; E submitted before the window
    r += _request("D", 130, 131, 140, None, outcome="preempted", chunks=1,
                  first_launch=6, last_launch=6, behind_launch=0)
    r += [_span("Dq2", "request/queued", 140, 160, trace="D"),
          _span("Dp2", "request/prefill", 160, 175, trace="D", chunks=1,
                first_launch=7, last_launch=7, behind_launch=0),
          _span("Dd", "request/decode", 175, 190, trace="D", tokens=2,
                outcome="finished", reason="length")]
    r += _request("F", 190, None)
    r += _request("G", 185, 186, 195, None, chunks=1, first_launch=99,
                  last_launch=99, behind_launch=0)
    r += _launch(0, T288, -9, -8, -8, -3)
    r += _request("E", -10, -9.5, -2, 30, chunks=1, first_launch=0,
                  last_launch=0, behind_launch=0)
    return r


def _facts(**more):
    # the harness's own tracks: (prompt, output, submitted s, token times s),
    # each submit 20 us before its span opened, its first stamp 10 us after
    # the request's first token
    tracks = [(64, 6, (10 * MS - 20 * US) / 1e9,
               [(40 * MS + 10 * US) / 1e9] * 6),
              (500, 6, (50 * MS - 20 * US) / 1e9,
               [(97 * MS + 10 * US) / 1e9] * 6),
              (64, 3, (110 * MS - 20 * US) / 1e9,
               [(151 * MS + 10 * US) / 1e9] * 3),
              (64, 1, (160 * MS - 20 * US) / 1e9,
               [(171 * MS + 10 * US) / 1e9])]
    return {"tracer_records": _records(), "t_start_ns": 0,
            "t_stop_ns": 200 * MS, "tracks": tracks,
            "ttft_ms": [31.0, 48.0, 42.0, 12.0],
            "gen_late_ms": [1.0, 1.0, 1.0, 1.0], **more}


def test_the_four_phases_add_up():
    got = request_phase_ms.requests(_facts())
    a, b, c, h = got["kept"]
    ms = lambda x: {k: x[k] / MS for k in request_phase_ms.PHASES}  # noqa
    assert ms(a) == pytest.approx(
        {"queued": 1.998, "behind": 3, "prefill": 23, "handout": 2})
    assert ms(b) == pytest.approx(
        {"queued": 11.998, "behind": 5, "prefill": 28, "handout": 2})
    # launch 6 starts when the wait for launch 5 ended, not when its own
    # dispatch span closed
    assert ms(c) == pytest.approx(
        {"queued": 0.998, "behind": 14, "prefill": 25, "handout": 1})
    assert ms(h) == pytest.approx(
        {"queued": 0.998, "behind": 2, "prefill": 7, "handout": 1})
    for x, span in zip(got["kept"], (30, 47, 41, 11)):
        # first token less submit, but for the 2 us between two phases
        assert x["sum"] == sum(x[k] for k in request_phase_ms.PHASES)
        assert x["sum"] == span * MS - 2 * US
    assert [x["chunks"] for x in got["kept"]] == [1, 2, 1, 1]
    assert [x["behind_launch"] for x in got["kept"]] == [0, 2, 5, 0]


def test_the_requests_left_out_are_counted():
    got = request_phase_ms.requests(_facts())
    # E was submitted before the window: not one of its requests
    assert got["n"] == 7 and len(got["kept"]) == 4
    assert dict(got["left"]) == {"preempted": 1, "no first token": 1,
                                 "no launch record": 1}
    # a gap between two phases wider than the tolerance: left out, counted
    facts = _facts()
    for r in facts["tracer_records"]:
        if r["span_id"] == "Aq":
            r["t1_ns"] -= request_phase_ms.TOL_NS
    got = request_phase_ms.requests(facts)
    assert len(got["kept"]) == 3
    assert got["left"]["parts do not add up"] == 1


def test_held_ticks_are_the_packs_a_queued_span_covers():
    got = request_phase_ms.requests(_facts())
    # B sat in the queue over both packs of tick t5 (one tick, under the
    # rule of its last pack); the pack that admitted it ends after its span
    assert [x["holds"] for x in got["kept"]] == \
        [{}, {"t5": request_phase_ms.NOT_PACKED}, {}, {}]
    assert got["depths"] == [1, 1]


@pytest.mark.parametrize("what, q, want", [
    ("queued", 50, (0.998 + 1.998) / 2), ("queued", 100, 11.998),
    ("behind", 50, 4.0), ("prefill", 50, 24.0), ("handout", 50, 1.5),
    ("hold_ticks", None, 0.25), ("chunks", None, 1.25)])
def test_the_metrics(what, q, want):
    ctx = Ctx()
    args = {"what": what} if q is None else {"what": what, "q": q}
    assert request_phase_ms.read(_facts(), args, ctx) == pytest.approx(want)


def test_the_log():
    ctx, facts = Ctx(), _facts()
    for what in ("queued", "behind"):
        request_phase_ms.read(facts, {"what": what, "q": 50}, ctx)
    text = "\n".join(ctx.lines)
    assert len(ctx.lines) == 7                  # once a run
    assert "7 requests submitted in the window, 4 kept; left out: " \
           "1 no first token, 1 no launch record, 1 preempted" in text
    assert "queued 1.498 / 8.998 / 3.998" in text
    # sums 29.998, 46.998, 40.998, 10.998; the harness's stamps lie 30 us
    # outside each: 20 before the submit, 10 after the token
    assert "the sum's p50 35.498 ms beside the harness's ttft_ms p50 " \
           "36.500 and gen_late_ms p50 1.000" in text
    assert "matched by submit order 4 of 4" in text
    assert "exceeds the sum by p50 0.032 / at most 0.032 ms" in text
    assert "prompt_tokens differ in 0; of 2 whose decode phase closed, " \
           "tokens + 1 differs from the tokens the harness counted in 0" \
           in text
    assert "1 held ticks" in text and f"{request_phase_ms.NOT_PACKED} 1" \
        in text
    assert "level 2: p50 ms queued 1.498, behind 2.500, prefill 15.000, " \
           "handout 1.500, sum 20.498; decode_step 2: p50 ms queued 6.498, " \
           "behind 9.500, prefill 26.500" in text
    assert "finished/length 3, open/- 1" in text
    assert "no traced stretch" in text


def test_a_parent_without_the_spans_reports_nothing():
    facts = _facts()
    facts["tracer_records"] = [
        r for r in facts["tracer_records"] if r["name"] != "request/queued"]
    ctx = Ctx()
    assert request_phase_ms.read(facts, {"what": "queued", "q": 50},
                                 ctx) is None
    assert ctx.lines == []
    assert request_phase_ms.read({"tracer_records": []},
                                 {"what": "chunks"}, ctx) is None


def test_the_estimate_agrees_with_executions_on_a_level_plane():
    """Executions that start where the estimate says and end where the wait
    returned, on a device plane level with the host's: both errors 0 for
    the requests whose launches were joined to whole executions (A's launch
    1 is the stretch's first execution, cut)."""
    runs = [(1, T288, 15, 38), (2, DEC, 46, 66), (3, T288, 67, 79),
            (4, T288, 81, 95), (5, DEC, 106, 125), (6, T288, 125, 150),
            (7, T288, 163, 170), (8, DEC, 196, 199)]
    mods = [(0, int(s * MS) + OFF, int(e * MS) + OFF, prog)
            for _, prog, s, e in runs]
    facts = _facts()
    facts["tracer_records"] += _launch(8, DEC, 195, 196, 196, 199)
    facts["view"] = TraceView([], [HostEvent(
        "main", "bench/clock_sync", 7 * MS + OFF, 10 * US)])
    facts["capture"] = {"mono_sync_ns": 7 * MS}
    facts["_launch_executions"] = _launches.executions_of(
        mods, [(s, e) for _, s, e, _ in mods])
    ctx = Ctx()
    request_phase_ms.read(facts, {"what": "behind", "q": 50}, ctx)
    line = ctx.lines[-1]
    assert "3 of 4 kept requests had their first and last launch joined" \
        in line
    assert "behind 0.000 / 0.000 ms" in line
    assert "prefill 0.000 / 0.000 ms" in line
    # the device plane 0.5 ms late: the estimate of behind is that much
    # early, prefill does not move
    facts = _facts()
    facts["tracer_records"] += _launch(8, DEC, 195, 196, 196, 199)
    facts["view"] = TraceView([], [HostEvent(
        "main", "bench/clock_sync", 7 * MS + OFF, 10 * US)])
    facts["capture"] = {"mono_sync_ns": 7 * MS}
    late = [(d, s + MS // 2, e + MS // 2, p) for d, s, e, p in mods]
    facts["_launch_executions"] = _launches.executions_of(
        late, [(s, e) for _, s, e, _ in late])
    ctx = Ctx()
    request_phase_ms.read(facts, {"what": "behind", "q": 50}, ctx)
    assert "behind -0.500 / -0.500 ms" in ctx.lines[-1]
    assert "prefill 0.000 / 0.000 ms" in ctx.lines[-1]


# ------------------------------------------------------------------ #
# rehearsal: a tiny serving cell of each kind reports its six metrics
# ------------------------------------------------------------------ #
@pytest.mark.parametrize("cell, prefix, extra", [
    ("serve-mistral7b-chat-steady", "chat_", {"chat_ttft_queue_ms_p90"}),
    ("serve-mistral7b-longprompt-closed", "", {"prompt_chunks_mean"})])
def test_rehearsal_reports_the_request_phases(cell, prefix, extra):
    from benchmark import run
    from benchmark.tests.rehearsal_sizes import TINY

    out = run.run_cell(cell, 5, 2.0, True, overrides=TINY[cell],
                       allow_cpu=True)
    facts = out.pop("_facts")
    want = {f"{prefix}ttft_{p}_ms_p50" for p in
            ("queue", "behind", "prefill", "handout")} \
        | {prefix + "queue_hold_ticks_mean"} | extra
    assert want <= set(out["metrics"])
    assert all(out["metrics"][m]["value"] >= 0 for m in want)
    got = request_phase_ms.requests(facts)
    assert got["kept"] and not got["left"]["parts do not add up"]
    for x in got["kept"]:
        assert all(x[p] >= 0 for p in request_phase_ms.PHASES)
        assert x["first"]["launch"] <= x["last"]["launch"]
