"""The four readers PR 23 brought, on hand-made Tracer records and
intervals: a pure-decode tick and a mixed tick with known spans and
counters, two Mosaic calls with different ``kernel_metadata``, and a scope
map of operations by ``op_name``."""

import os

import pytest

from benchmark.lib import tracing
from benchmark.lib.tracing import DeviceEvent, HostEvent, TraceView
from benchmark.readers import (_tick_tree, kernel_meta_ms, scope_ms,
                               span_ms_tick, tick_attr_ratio)

MS = 1_000_000


class Ctx:
    def __init__(self):
        self.lines = []
        self.peaks = None

    def log(self, msg):
        self.lines.append(msg)


def _span(sid, name, t0, t1, parent=None, **attrs):
    rec = {"name": name, "ph": "X", "tid": "main", "trace_id": "t",
           "span_id": sid, "parent": parent, "t0_ns": t0, "t1_ns": t1}
    if attrs:
        rec["attrs"] = attrs
    return rec


def _records():
    """Window 0 .. 100 ms: a decode tick (0-16 ms), a mixed tick (20-60
    ms), a second decode tick (60-76 ms); a decode tick of the pre-roll
    before it and one that ends after it."""
    r = []
    for i, t0 in enumerate((0, 60 * MS)):
        tk, ph = f"d{i}", f"d{i}p"
        r += [_span(tk, "tick", t0, t0 + 16 * MS, tick=i, kind="decode",
                    emitted=30 + i),
              _span(tk + "k", "pack", t0, t0 + MS // 10, tk),
              _span(ph, "decode", t0 + MS // 10, t0 + 16 * MS, tk),
              _span(tk + "a", "engine/decode_prep", t0 + MS // 10,
                    t0 + MS // 2, ph),
              _span(tk + "b", "engine/decode_step", t0 + MS // 2,
                    t0 + MS, ph),
              _span(tk + "c", "fetch", t0 + MS, t0 + 15 * MS, ph),
              _span(tk + "d", "advance", t0 + 15 * MS, t0 + 16 * MS, ph)]
    r += [_span("m0", "tick", 20 * MS, 60 * MS, kind="mixed", emitted=11),
          _span("m0p", "prefill", 21 * MS, 59 * MS, "m0"),
          _span("m0a", "engine/build_batch", 21 * MS, 24 * MS, "m0p",
                tokens=700, bucket=1024),
          _span("m0b", "engine/ragged_step", 24 * MS, 25 * MS, "m0p"),
          _span("m0c", "engine/fetch_logits", 25 * MS, 59 * MS, "m0p"),
          _span("m0s", "sample", 59 * MS, 60 * MS, "m0"),
          _span("m0d", "advance", 59 * MS, 60 * MS, "m0s"),
          # a prefill tick whose chunks spilled into a second ragged batch
          _span("p0", "tick", 80 * MS, 90 * MS, kind="prefill", emitted=1),
          _span("p0p", "prefill", 80 * MS, 89 * MS, "p0"),
          _span("p0a", "engine/build_batch", 80 * MS, 80 * MS + MS // 2,
                "p0p", tokens=60, bucket=128),
          _span("p0b", "engine/build_batch", 84 * MS, 84 * MS + MS // 2,
                "p0p", tokens=40, bucket=128)]
    # outside the window: not counted
    r += [_span("pre", "tick", -20 * MS, -4 * MS, kind="mixed"),
          _span("prea", "engine/build_batch", -19 * MS, -5 * MS, "pre",
                tokens=9, bucket=16),
          _span("preb", "fetch", -19 * MS, -5 * MS, "pre"),
          _span("late", "tick", 95 * MS, 111 * MS, kind="decode"),
          _span("req", "request/decode", 0, 50 * MS)]
    return r


def _facts(**more):
    return {"tracer_records": _records(), "t_start_ns": 0,
            "t_stop_ns": 100 * MS, **more}


def test_ticks_of_a_kind_and_what_descends_from_them():
    facts = _facts()
    assert [t["span_id"] for t in _tick_tree.kind_ticks(facts, "decode")] \
        == ["d0", "d1"]
    assert [t["span_id"] for t in
            _tick_tree.kind_ticks(facts, "mixed+prefill")] == ["m0", "p0"]
    ticks, under = _tick_tree.descendants(facts, "decode")
    assert [t["span_id"] for t in ticks] == ["d0", "d1"]
    assert sorted(r["span_id"] for r in under) == sorted(
        f"d{i}{k}" for i in (0, 1) for k in "kpabcd")
    # two levels below the tick, and nothing of the pre-roll's mixed tick
    assert sorted(r["span_id"] for r in
                  _tick_tree.descendants(facts, "mixed")[1]) == [
        "m0a", "m0b", "m0c", "m0d", "m0p", "m0s"]
    # a program from before the counters: no tick has a kind
    old = [dict(r, attrs={}) for r in _records()]
    assert _tick_tree.kind_ticks(dict(_facts(), tracer_records=old),
                                 "decode") == []


@pytest.mark.parametrize("names, kind, want", [
    (["engine/decode_prep"], "decode", 0.4),
    (["fetch"], "decode", 14.0),
    (["advance"], "decode", 1.0),
    (["engine/decode_prep", "engine/decode_step", "fetch", "advance"],
     "decode", 15.9),
    (["engine/build_batch"], "mixed", 3.0),
    (["engine/build_batch"], "mixed+prefill", 2.0),
    (["advance"], "mixed", 1.0),             # two levels below the tick
    (["fetch"], "verify", None),             # no such tick: left out
])
def test_span_ms_tick(names, kind, want):
    ctx = Ctx()
    got = span_ms_tick.read(_facts(), {"names": names, "kind": kind}, ctx)
    assert got == (pytest.approx(want) if want is not None else None)
    if want is not None:
        assert len(ctx.lines) == 1 and "host ms per tick by span" in \
            ctx.lines[0]


def test_span_ms_tick_logs_once_a_kind():
    ctx, facts = Ctx(), _facts()
    for names in (["fetch"], ["advance"]):
        span_ms_tick.read(facts, {"names": names, "kind": "decode"}, ctx)
    assert len(ctx.lines) == 1
    assert "2 decode ticks" in ctx.lines[0] and "tick 16.000" in \
        ctx.lines[0] and "fetch 14.000" in ctx.lines[0]


def test_tick_attr_ratio():
    facts, ctx = _facts(), Ctx()
    args = {"num": "tokens", "den": "bucket"}
    assert tick_attr_ratio.read(facts, dict(args, kind="mixed"), ctx) == \
        pytest.approx(100 * 700 / 1024)
    assert tick_attr_ratio.read(facts, dict(args, kind="mixed+prefill"),
                                ctx) == pytest.approx(100 * 800 / 1280)
    # decode ticks run no ragged batch: nothing owns such a counter there
    assert tick_attr_ratio.read(facts, dict(args, kind="decode"), ctx) is None
    assert tick_attr_ratio.read(facts, dict(args, kind="verify"), ctx) is None
    assert tick_attr_ratio.read({}, dict(args, kind="mixed"), ctx) is None


def _call(dev, instr, kernel, start, dur):
    meta = '{\n"kernel":"%s"\n}' % kernel if kernel else "{}"
    text = (f'%{instr} = bf16[8,20,1024,64]{{3,2,1,0}} custom-call('
            f'bf16[8,20,1024,64]{{3,2,1,0}} %p), '
            f'custom_call_target="tpu_custom_call", '
            f'frontend_attributes={{kernel_metadata={meta}}}')
    return DeviceEvent(dev, text, tracing.label_of(text), start, dur)


def test_kernel_meta_ms_tells_kernels_apart():
    view = TraceView([
        _call(0, "h_3.1", "_fwd_kernel_onepass", 0, 2 * MS),
        _call(0, "h_3.2", "_bwd_dq_kernel", 10 * MS, 3 * MS),
        _call(0, "h_3.3", "_bwd_dkv_kernel", 20 * MS, 5 * MS),
        _call(0, "_fwd_kernel_onepass.9", "_fwd_kernel_onepass", 30 * MS,
              2 * MS),
        _call(0, "h_2.7", None, 40 * MS, 7 * MS),        # no name: nobody's
        DeviceEvent(0, "%fusion.1 = f32[8]{0} fusion(f32[8]{0} %x)",
                    "fusion.1 fusion", 50 * MS, MS)], [])
    facts, ctx = {"view": view, "traced_steps": 2}, Ctx()
    got = {p: kernel_meta_ms.read(facts, {"pattern": p, "per": "step"}, ctx)
           for p in ("^_fwd_kernel", "^_bwd_dq_kernel", "^_bwd_dkv_kernel")}
    assert got == {"^_fwd_kernel": pytest.approx(2.0),
                   "^_bwd_dq_kernel": pytest.approx(1.5),
                   "^_bwd_dkv_kernel": pytest.approx(2.5)}
    assert "_fwd_kernel_onepass 2.000" in ctx.lines[0]
    # the three add up to what the accepted reader gives for the named calls
    assert sum(got.values()) == pytest.approx(
        1e3 * view.seconds_matching(" tpu_custom_call$") / 2 - 3.5)
    assert kernel_meta_ms.read(facts, {"pattern": "^_kernel$",
                                       "per": "step"}, ctx) is None
    assert kernel_meta_ms.kernel_of("%fusion.1 = f32[8]{0} fusion()") is None
    assert kernel_meta_ms.kernel_of(
        'frontend_attributes={kernel_metadata={"kernel":"_kernel"}}') == \
        "_kernel"


def test_kernel_meta_ms_averages_over_devices():
    view = TraceView([_call(0, "a.1", "_fwd_kernel", 0, 2 * MS),
                      _call(1, "a.1", "_fwd_kernel", 0, 4 * MS)], [])
    assert kernel_meta_ms.read({"view": view, "traced_steps": 1},
                               {"pattern": "^_fwd", "per": "step"},
                               Ctx()) == pytest.approx(3.0)


@pytest.mark.parametrize("op_name, key", [
    ("jit(run)/layers_3/attn/dense_read/dot_general", "attn/dense_read"),
    ("jit(run)/layers_0/mlp/jit(silu)/logistic", "mlp"),
    ("jit(run)/sample_argmax/reduce", "sample_argmax"),
    ("jit(fused)/jvp(GPT2LMHeadModel)/h_0/mlp/c_fc/dot_general", "mlp/c_fc"),
    ("jit(fused)/transpose(jvp(MistralForCausalLM))/model/layers_1/"
     "self_attn/shard_map/_bwd_dq_kernel/pallas_call",
     "self_attn/_bwd_dq_kernel"),
    ("jit(fused)/optimizer/adamw/mul", "optimizer/adamw"),
    ("jit(fused)/add", "(no scope)"),
])
def test_scope_key(op_name, key):
    assert scope_ms.scope_key(op_name) == key


def _scope_facts():
    """Two pure-decode ticks (0-10, 40-50 ms) around a mixed tick (10-40
    ms); the operations of a decode program under three scopes, and one
    that straddles a tick's end."""
    host = [HostEvent("python3", "bench/tick", 0, 10 * MS),
            HostEvent("python3", "engine/decode_step", 1 * MS, MS // 2),
            HostEvent("python3", "bench/tick", 10 * MS, 30 * MS),
            HostEvent("python3", "engine/ragged_step", 11 * MS, MS),
            HostEvent("python3", "bench/tick", 40 * MS, 10 * MS),
            HostEvent("python3", "engine/decode_step", 41 * MS, MS // 2)]
    dev = [DeviceEvent(0, "x", "x", 0, 50 * MS)]
    ops = []
    for t0 in (0, 40 * MS):
        ops += [(0, t0 + 1 * MS, t0 + 3 * MS,
                 "jit(run)/layers_0/attn/dense_read/dot_general",
                 "fusion.1 fusion"),
                (0, t0 + 3 * MS, t0 + 7 * MS,
                 "jit(run)/layers_0/mlp/dot_general", "fusion.2 fusion"),
                (0, t0 + 7 * MS, t0 + 8 * MS, "jit(run)/lm_head/dot_general",
                 "fusion.3 fusion"),
                (0, t0 + 8 * MS, t0 + 8 * MS + MS // 2,
                 "jit(run)/sample_argmax/reduce", "reduce.1 reduce")]
    # a mixed tick's MLP: outside the pure-decode ticks
    ops.append((0, 12 * MS, 30 * MS, "jit(run)/layers_0/mlp/dot_general",
                "fusion.9 fusion"))
    # starts in the last decode tick, ends 1 ms after it
    ops.append((0, 49 * MS, 51 * MS, "jit(run)/layers_1/mlp/dot_general",
                "fusion.2 fusion"))
    return {"view": TraceView(dev, host), "_scope_events": ops,
            "traced_steps": 4}


@pytest.mark.parametrize("args, want", [
    ({"scope": "/attn/dense_read/", "per": "decode_tick"}, 2.0),
    ({"scope": "/mlp/", "per": "decode_tick"}, 4.5),
    ({"scope": "/(lm_head|sample_argmax)(/|$)", "per": "decode_tick"}, 1.5),
    ({"scope": "/mlp/", "per": "step"}, (8 + 18 + 2) / 4),
    ({"scope": "/mlp/", "per": "step", "exclude": r"^fusion\.9 "}, 2.5),
    ({"scope": "/optimizer/", "per": "step"}, None),
])
def test_scope_ms(args, want):
    ctx = Ctx()
    got = scope_ms.read(_scope_facts(), args, ctx)
    assert got == (pytest.approx(want) if want is not None else None)
    assert "by scope" in ctx.lines[0]
    # an absent scope is said aloud, not only left out
    assert ["no such scope" in ln for ln in ctx.lines[1:]] == \
        ([] if want is not None else [True])


def test_scope_ms_without_a_trace_or_without_op_names():
    assert scope_ms.read({"view": None}, {"scope": "/mlp/", "per": "step"},
                         Ctx()) is None
    facts = dict(_scope_facts(), _scope_events=[])
    assert scope_ms.read(facts, {"scope": "/mlp/", "per": "step"},
                         Ctx()) is None


# --------------------------------------------------------------------- #
# lib/xplane_ops.py: the op_name the profile keeps per instruction
# --------------------------------------------------------------------- #
def _varint(n):
    out = bytearray()
    while True:
        b = n & 0x7F
        n >>= 7
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


def _f(no, value):
    """One protobuf field: an int as a varint, bytes/str length-delimited."""
    if isinstance(value, int):
        return _varint(no << 3) + _varint(value)
    if isinstance(value, str):
        value = value.encode()
    return _varint(no << 3 | 2) + _varint(len(value)) + value


def _entry(key, body):
    return _f(1, key) + _f(2, body)


def test_xplane_ops_reads_the_wire_format(tmp_path):
    """A hand-made XSpace: one TPU plane with two instructions, one carrying
    ``tf_op`` as a string and one as a reference to a statistic's name, and
    a host plane that is skipped."""
    from benchmark.lib import xplane_ops

    stat_meta = (_f(5, _entry(7, _f(1, 7) + _f(2, "tf_op")))
                 + _f(5, _entry(8, _f(1, 8) + _f(2, "flops")))
                 + _f(5, _entry(9, _f(1, 9) + _f(2, "jit(run)/lm_head/dot:"))))
    ev_meta = (
        _f(4, _entry(1, _f(1, 1) + _f(2, "%fusion.1 = f32[8]{0} fusion()")
                     + _f(5, _f(1, 8) + _f(4, 123))
                     + _f(5, _f(1, 7) + _f(5, "jit(run)/layers_0/mlp/dot:"))))
        + _f(4, _entry(2, _f(1, 2) + _f(2, "%fusion.2 = f32[8]{0} fusion()")
                       + _f(5, _f(1, 7) + _f(7, 9))))
        + _f(4, _entry(3, _f(1, 3) + _f(2, "%copy-done.1 = f32[8]{0} c()"))))
    events = (_f(4, _f(1, 1) + _f(2, 5_000_000) + _f(3, 2_000_000))
              + _f(4, _f(1, 2) + _f(2, 8_000_000) + _f(3, 1_500_999))
              + _f(4, _f(1, 3) + _f(2, 9_600_000) + _f(3, 100_000)))
    line = _f(1, 1) + _f(2, "XLA Ops") + _f(3, 1000) + events
    other = _f(1, 2) + _f(2, "Steps") + _f(3, 1000) + \
        _f(4, _f(1, 1) + _f(2, 0) + _f(3, 9))
    tpu = _f(1, 1) + _f(2, "/device:TPU:1") + _f(3, line) + _f(3, other) + \
        ev_meta + stat_meta
    host = _f(1, 2) + _f(2, "/host:CPU") + _f(3, line)
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(_f(1, tpu) + _f(1, host))
    assert xplane_ops.device_ops(str(path)) == [
        (1, 6000, 8000, "jit(run)/layers_0/mlp/dot:",
         "%fusion.1 = f32[8]{0} fusion()"),
        (1, 9000, 10500, "jit(run)/lm_head/dot:",
         "%fusion.2 = f32[8]{0} fusion()"),
        (1, 10600, 10700, "", "%copy-done.1 = f32[8]{0} c()")]
    assert [o[3] for o in xplane_ops.device_ops(str(path), "flops")] == \
        ["", "", ""]                       # an integer statistic: not text


FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "fixtures")


@pytest.mark.parametrize("name", ["serve_chat_1chip", "train_gpt2_d64_1chip",
                                  "serve_chat_pr23_1chip"])
def test_xplane_ops_agrees_with_profile_data(name):
    """Same events, names and times as ``jax.profiler.ProfileData`` gives
    the reducer, on the recorded traces."""
    from benchmark.lib import xplane_ops

    path = f"{FIXTURES}/{name}.xplane.pb"
    view = TraceView.from_xplane(path)
    ops = xplane_ops.device_ops(path)
    assert sorted((d, s, e, t) for d, s, e, _o, t in ops) == \
        sorted((e.device, e.start, e.end, e.name) for e in view.device_events)
    carry = sum(1 for o in ops if o[3])
    # PR 22's fixtures were cut without statistics
    assert (carry > 0) == (name == "serve_chat_pr23_1chip")


def test_recorded_trace_pr23():
    """What the new readers must give on the stretch recorded in PR 23: a
    mixed tick on the 256 bucket and two pure-decode ticks."""
    path = f"{FIXTURES}/serve_chat_pr23_1chip.xplane.pb"
    view = TraceView.from_xplane(path)
    assert len(view.device_events) == 2726
    assert sum(e.dur for e in view.device_events) == 92371226
    assert [e.dur for e in view.host_named(r"^bench/tick$")] == \
        [71465189, 15561050, 15468839]
    assert len(view.host_named(r"^engine/decode_prep$")) == 2
    assert len(view.host_named(r"^engine/build_batch$")) == 1
    facts, ctx = {"view": view, "capture": {"xplane": path}}, Ctx()
    got = {s: scope_ms.read(facts, {"scope": s, "per": "decode_tick"}, ctx)
           for s in ("/attn/dense_read/", "/mlp/",
                     "/(lm_head|sample_argmax)(/|$)", "/optimizer/")}
    assert got == {"/attn/dense_read/": pytest.approx(2.585034),
                   "/mlp/": pytest.approx(7.4655925),
                   "/(lm_head|sample_argmax)(/|$)": pytest.approx(0.3635005),
                   "/optimizer/": None}
    assert "mlp 7.466, attn/dense_read 2.585, attn/out_proj 0.740" in \
        ctx.lines[0]
    # the mixed tick's Mosaic calls say which kernel they are
    assert kernel_meta_ms.read(facts, {"pattern": "^_kernel$", "per": "tick"},
                               ctx) == pytest.approx(18.471365333)
    assert kernel_meta_ms.read(facts, {"pattern": "^_prefill_kernel$",
                                       "per": "tick"}, ctx) is None
    # ... and the accepted pattern still finds them by instruction name
    assert 1e3 * view.seconds_matching(
        r"^paged_attention[.\d]* .*tpu_custom_call") / 3 == \
        pytest.approx(18.471365333)
