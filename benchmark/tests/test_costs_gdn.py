"""``lib/costs_gdn.py`` and ``families/qwen3_next.py::shapes`` against
values worked out by hand, and the two readers this cell brought
(``gdn_roofline_pct``, ``tick_weighted_attr_pct``) on hand-made events."""

import types

import pytest

from benchmark.families import qwen3_next
from benchmark.lib import costs, costs_gdn, spec, tracing
from benchmark.readers import gdn_roofline_pct, tick_weighted_attr_pct

PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def _cfg():
    return spec.load_json(spec.BENCH_DIR +
                          "/configs/qwen3-next-80b-a3b-serve-1chip.json")


def test_qwen3next_shapes_by_hand():
    s = qwen3_next.shapes(_cfg())
    expert = 3 * 2048 * 512
    assert expert == 3_145_728 and 128 * expert == 402_653_184
    # router 2048 x 512, shared expert, its gate
    fixed = 2048 * 512 + expert + 2048
    assert fixed == 4_196_352                          # "4.2 M"
    gdn = 2048 * 12288 + 2048 * 64 + 4096 * 2048       # qkvz, ba, out
    attn = 2048 * 8192 + 2 * 2048 * 512 + 4096 * 2048
    assert (gdn, attn) == (33_685_504, 27_262_976)     # "33.7 M", "27.3 M"
    small = 4 * 8192 + 2 * 32 + 128                    # conv, A_log, dt, norm
    gdn_layer = gdn + small + fixed + 128 * expert + 2 * 2048
    attn_layer = attn + 2 * 256 + fixed + 128 * expert + 2 * 2048
    assert round(gdn_layer / 1e6, 1) == 440.6
    assert round(attn_layer / 1e6, 1) == 434.1
    assert (s["layers"], s["gdn_layers"], s["attn_layers"]) == (8, 6, 2)
    assert s["total_params"] == 6 * gdn_layer + 2 * attn_layer \
        + 2 * 2048 * 37984 + 2048
    assert round(s["total_params"] * 2 / 1e9, 2) == 7.33       # bf16 GB
    assert (s["experts"], s["router_width"], s["experts_per_token"],
            s["expert_width"]) == (128, 512, 10, 512)
    # one token multiplies by 2.5 routed experts here on average
    assert s["matmul_params"] == 6 * gdn + 2 * attn \
        + 8 * (fixed + 10 * 128 * expert // 512) + 2048 * 37984
    # KV: 2 attention layers x 2 heads x 256 x (k, v) x 2 bytes
    assert s["kv_bytes_per_token"] == 4096
    assert 512 * 128 * s["kv_bytes_per_token"] == 268_435_456  # 0.27 GB
    # state: 32 x 128 x 128 float32 + 3 x 8192 bf16, six layers
    assert s["state_bytes_per_seq"] == 6 * (2_097_152 + 49_152)
    assert s["state_slots"] == 32
    assert 33 * s["state_bytes_per_seq"] == 424_968_192        # 0.42 GB


def test_rule_costs_by_hand():
    s = qwen3_next.shapes(_cfg())
    assert costs_gdn.state_matrix_bytes(s) == 2_097_152
    # 32 heads x (7 x 128 x 128 + 2 x 128)
    assert costs_gdn.token_flops(s) == 32 * (7 * 16384 + 256) == 3_678_208
    assert costs_gdn.token_row_bytes(s) == 32 * (4 * 128 + 2) * 4 == 65_792
    # a decode step of 32 sequences: every slot read and written
    flops, nbytes = costs_gdn.step_costs(s, 32)
    assert flops == 6 * 32 * 3_678_208
    assert nbytes == 6 * 32 * (2 * 2_097_152 + 65_792) == 817_938_432
    r = costs.roofline(flops, nbytes, 2e-3, PEAKS)
    assert r["bound"] == "memory"
    assert r["least_s"] == pytest.approx(817_938_432 / 819e9)  # ~1.0 ms
    # a batch with 1024 prompt tokens of 2 sequences: the rows' bytes rule
    flops, nbytes = costs_gdn.chunk_costs(s, 1024, 2)
    assert flops == 6 * 1024 * 3_678_208
    assert nbytes == 6 * (2 * 2 * 2_097_152 + 1024 * 65_792)
    r = costs.roofline(flops, nbytes, 1.0, PEAKS)
    assert r["bound"] == "memory"
    assert r["least_s"] == pytest.approx(nbytes / 819e9)       # ~0.55 ms


# ------------------------------------------------------------------ #
# the readers, on hand-made events
# ------------------------------------------------------------------ #
def _kernel(start, dur, kernel):
    text = ('%k = f32[32,32,128] custom-call(), custom_call_target='
            '"tpu_custom_call", frontend_attributes={kernel_metadata='
            '{"kernel":"' + kernel + '"}}')
    return tracing.DeviceEvent(device=0, name=text,
                               label=tracing.label_of(text), start=start,
                               dur=dur)


def _host(name, start, dur):
    return tracing.HostEvent("main", name, start, dur)


def _facts(device_events, host_events, spans, layers=2):
    s = dict(qwen3_next.shapes(_cfg()), gdn_layers=layers)
    # the profiler's clock runs 1000 ns ahead of the Tracer's
    host_events = host_events + [_host("bench/clock_sync", 1000, 1)]
    recs = [{"ph": "X", "name": n, "t0_ns": t, "t1_ns": t + 1, "attrs": a}
            for n, t, a in spans]
    return {"view": tracing.TraceView(device_events, host_events),
            "shapes": s, "tracer_records": recs,
            "capture": {"mono_sync_ns": 0}}


def _ctx(peaks=PEAKS):
    logs = []
    return types.SimpleNamespace(peaks=peaks, log=logs.append), logs


def test_step_roofline_sums_over_the_pure_decode_ticks():
    ms = 1_000_000
    # two pure-decode ticks and a mixed one between them (profiler clock)
    host = [_host("bench/tick", 10 * ms, 10 * ms),
            _host("engine/decode_step", 11 * ms, ms),
            _host("bench/tick", 20 * ms, 10 * ms),          # mixed
            _host("bench/tick", 30 * ms, 10 * ms),
            _host("engine/decode_step", 31 * ms, ms)]
    # 2 layers: two calls a forward, 0.5 ms each; the mixed tick's count
    # for nothing
    dev = [_kernel(12 * ms, ms // 2, "_gdn_step_kernel"),
           _kernel(13 * ms, ms // 2, "_gdn_step_kernel"),
           _kernel(22 * ms, ms, "_gdn_step_kernel"),
           _kernel(23 * ms, ms, "_gdn_step_kernel"),
           _kernel(32 * ms, ms // 2, "_gdn_step_kernel"),
           _kernel(33 * ms, ms // 2, "_gdn_step_kernel"),
           _kernel(14 * ms, 5 * ms, "_gmm_kernel")]
    spans = [("engine/decode_prep", 11 * ms - 1000, {"seqs": 32}),
             ("engine/decode_prep", 31 * ms - 1000, {"seqs": 30}),
             ("engine/build_batch", 21 * ms - 1000,
              {"tokens": 600, "chunk_tokens": 580, "chunk_seqs": 2})]
    ctx, logs = _ctx()
    got = gdn_roofline_pct.read(
        _facts(dev, host, spans),
        {"pattern": "^_gdn_step_kernel$", "which": "step"}, ctx)
    s = dict(qwen3_next.shapes(_cfg()), gdn_layers=2)
    least = sum(costs_gdn.step_costs(s, n)[1] for n in (32, 30)) / 819e9
    assert got == pytest.approx(100 * least / 2e-3)
    assert 0 < got < 100 and "2 forwards" in logs[-1]


def test_chunk_roofline_counts_real_prompt_tokens():
    ms = 1_000_000
    dev = [_kernel(5 * ms, 2 * ms, "_gdn_chunk_kernel"),
           _kernel(8 * ms, 2 * ms, "_gdn_chunk_kernel"),
           _kernel(1 * ms, ms, "_gdn_step_kernel"),
           _kernel(11 * ms, ms, "_gdn_step_kernel")]
    spans = [("engine/build_batch", 4 * ms - 1000,
              {"tokens": 1040, "chunk_tokens": 1010, "chunk_seqs": 3}),
             # a batch of single-token rows: no tile, no chunk kernel call
             ("engine/build_batch", 10 * ms - 1000,
              {"tokens": 12, "chunk_tokens": 0, "chunk_seqs": 0}),
             # dispatched after the stretch
             ("engine/build_batch", 50 * ms,
              {"tokens": 900, "chunk_tokens": 900, "chunk_seqs": 1})]
    ctx, _logs = _ctx()
    got = gdn_roofline_pct.read(
        _facts(dev, [], spans),
        {"pattern": "^_gdn_chunk_kernel$", "which": "chunk"}, ctx)
    s = dict(qwen3_next.shapes(_cfg()), gdn_layers=2)
    flops, nbytes = costs_gdn.chunk_costs(s, 1010, 3)
    assert got == pytest.approx(
        100 * max(flops / 197e12, nbytes / 819e9) / 4e-3)
    assert 0 < got < 100


def test_roofline_readers_return_none_without_kernel_counters_or_peaks():
    ms = 1_000_000
    step = {"pattern": "^_gdn_step_kernel$", "which": "step"}
    chunk = {"pattern": "^_gdn_chunk_kernel$", "which": "chunk"}
    host = [_host("bench/tick", 0, 10 * ms),
            _host("engine/decode_step", ms, ms)]
    dev = [_kernel(2 * ms, ms, "_gdn_step_kernel"),
           _kernel(4 * ms, ms, "_gdn_chunk_kernel")]
    prep = [("engine/decode_prep", ms, {"seqs": 4})]
    ctx, _ = _ctx()
    # the XLA composition, or a program without the layer: no such call
    assert gdn_roofline_pct.read(
        _facts([_kernel(0, 5, "_gmm_kernel")], host, prep), step,
        ctx) is None
    # a program without the counters (the parent has no chunk_tokens)
    assert gdn_roofline_pct.read(
        _facts(dev, host, [("engine/build_batch", ms, {"tokens": 9})]),
        chunk, ctx) is None
    assert gdn_roofline_pct.read(
        _facts(dev, host, [("engine/decode_prep", ms, None)]), step,
        ctx) is None
    # a family without the layer
    facts = _facts(dev, host, prep)
    facts["shapes"] = {"layers": 2}
    assert gdn_roofline_pct.read(facts, step, ctx) is None
    # no peaks (not a TPU), no view
    assert gdn_roofline_pct.read(_facts(dev, host, prep), step,
                                 _ctx(None)[0]) is None
    facts = _facts(dev, host, prep)
    facts["view"] = None
    assert gdn_roofline_pct.read(facts, step, ctx) is None


def test_state_live_pct_weights_ticks_by_their_length():
    def tick(sid, t0, t1, kind="decode"):
        return {"ph": "X", "name": "tick", "span_id": sid, "parent": None,
                "t0_ns": t0, "t1_ns": t1, "attrs": {"kind": kind}}

    def under(sid, parent, name, attrs):
        return {"ph": "X", "name": name, "span_id": sid, "parent": parent,
                "t0_ns": 0, "t1_ns": 1, "attrs": attrs}

    recs = [tick("t1", 100, 200), under("d1", "t1", "decode", {}),
            under("p1", "d1", "engine/decode_prep",
                  {"seqs": 3, "state_slots": 32}),
            tick("t2", 200, 500, "mixed"),
            under("f2", "t2", "prefill", {}),
            under("b2", "f2", "engine/build_batch",
                  {"tokens": 9, "state_slots": 16}),
            under("b3", "f2", "engine/build_batch",
                  {"tokens": 9, "state_slots": 1}),    # the first counts
            tick("t3", 500, 900),                      # no dispatch: out
            tick("t4", 2000, 2100),                    # outside the window
            under("p4", "t4", "engine/decode_prep", {"state_slots": 1})]
    def facts(records, shapes):
        return {"tracer_records": records, "t_start_ns": 50,
                "t_stop_ns": 1000, "shapes": shapes}

    args = {"attr": "state_slots", "total": "state_slots"}
    got = tick_weighted_attr_pct.read(facts(recs, {"state_slots": 32}), args,
                                      _ctx()[0])
    assert got == pytest.approx(100 * (100 * 32 + 300 * 16) / (400 * 32))
    # a family without slots, a program without the counter
    assert tick_weighted_attr_pct.read(facts(recs, {"layers": 2}), args,
                                       _ctx()[0]) is None
    bare = [r for r in recs if r["name"] == "tick"]
    assert tick_weighted_attr_pct.read(facts(bare, {"state_slots": 32}),
                                       args, _ctx()[0]) is None
