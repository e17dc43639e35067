"""``lib/costs_moe.py`` and ``families/olmoe.py::shapes`` against values
worked out by hand, and ``readers/gmm_roofline_pct.py`` on hand-made
events."""

import types

import pytest

from benchmark.families import olmoe
from benchmark.lib import costs, costs_moe, spec, tracing
from benchmark.readers import gmm_roofline_pct

PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def _cfg():
    return spec.load_json(spec.BENCH_DIR +
                          "/configs/olmoe-1b-7b-0125-serve-1chip.json")


def test_olmoe_shapes_by_hand():
    s = olmoe.shapes(_cfg())
    experts = 64 * 3 * 2048 * 1024
    attn = 4 * 2048 * 2048
    assert experts == 402_653_184 and attn == 16_777_216
    # a layer: attention, router 2048 x 64, experts, two layer norms and
    # the q and k norms (2048 each)
    layer = attn + 2048 * 64 + experts + 4 * 2048
    assert layer == 419_569_664                      # "419.6 M a layer"
    assert s["layers"] == 10
    assert s["total_params"] == 10 * layer + 2 * 2048 * 50304 + 2048
    assert round(s["total_params"] * 2 / 1e9, 2) == 8.80      # bf16 GB
    # what one token multiplies by: 8 of the 64 experts
    assert s["matmul_params"] == 10 * (attn + 2048 * 64 + experts // 8) \
        + 2048 * 50304
    assert (s["experts"], s["experts_per_token"], s["expert_width"]) == \
        (64, 8, 1024)
    assert s["kv_bytes_per_token"] == 2 * 10 * 16 * 128 * 2 == 81_920
    # the pool of the configuration: 192 blocks of 128 tokens
    assert 192 * 128 * s["kv_bytes_per_token"] == 2_013_265_920


@pytest.mark.parametrize("tokens, rows, touched", [
    (32, 256, 64), (4, 32, 32), (544, 4352, 64), (1, 8, 8)])
def test_routed_rows_and_touched(tokens, rows, touched):
    s = olmoe.shapes(_cfg())
    assert costs_moe.routed_rows(s, tokens) == rows
    assert costs_moe.experts_touched_at_most(s, rows) == touched


def test_decode_tick_costs_by_hand():
    """32 sequences: 256 routed rows, every expert's weights, 10 layers."""
    s = olmoe.shapes(_cfg())
    flops, nbytes = costs_moe.grouped_ffn_costs(s, 256)
    assert flops == 10 * 3 * 2 * 256 * 2048 * 1024 == 32_212_254_720
    weights = 64 * 3 * 2048 * 1024 * 2           # 805 MB a layer
    acts = 3 * 256 * (2048 + 1024) * 2
    assert weights == 805_306_368
    assert nbytes == 10 * (weights + acts) == 8_100_249_600
    r = costs.roofline(flops, nbytes, 0.0125, PEAKS)
    assert r["bound"] == "memory"
    assert r["least_s"] == pytest.approx(8_100_249_600 / 819e9)   # 9.89 ms
    assert r["pct"] == pytest.approx(79.12, abs=0.01)


def test_full_mixed_tick_is_still_weight_bound():
    """1056 rows fed of which 1040 real: 8320 routed rows, 130 an expert:
    1024-wide experts are too small for that to reach the MXU's side of
    the roofline (the ridge is at 240 rows an expert)."""
    s = olmoe.shapes(_cfg())
    flops, nbytes = costs_moe.grouped_ffn_costs(s, 8320)
    assert flops == 10 * 3 * 2 * 8320 * 2048 * 1024
    r = costs.roofline(flops, nbytes, 1.0, PEAKS)
    # 1.047 TFLOP = 5.31 ms at the peak; 8.56 GB = 10.46 ms at the HBM peak
    assert flops / 197e12 == pytest.approx(5.314e-3, rel=1e-3)
    assert r["bound"] == "memory" and r["least_s"] == \
        pytest.approx(nbytes / 819e9)


# ------------------------------------------------------------------ #
# the reader, on hand-made events
# ------------------------------------------------------------------ #
def _event(start, dur, kernel="_gmm_kernel"):
    text = ('%gmm = bf16[256,512] custom-call(), custom_call_target='
            '"tpu_custom_call", frontend_attributes={kernel_metadata='
            '{"kernel":"' + kernel + '"}}')
    return tracing.DeviceEvent(device=0, name=text,
                               label=tracing.label_of(text), start=start,
                               dur=dur)


def _facts(events, spans, layers=2):
    s = dict(olmoe.shapes(_cfg()), layers=layers)
    view = types.SimpleNamespace(device_events=events, devices=[0])
    recs = [{"ph": "X", "name": n, "t0_ns": t, "t1_ns": t + 1,
             "attrs": a} for n, t, a in spans]
    return {"view": view, "shapes": s, "tracer_records": recs,
            "t_stop_ns": 10_000}


def _ctx():
    logs = []
    return types.SimpleNamespace(peaks=PEAKS, log=logs.append), logs


def test_reader_pairs_forwards_by_order_and_leaves_small_ones_out():
    # three forwards before the window's end; the trace caught the last two
    spans = [("engine/decode_prep", 100, {"seqs": 32}),
             ("engine/build_batch", 200, {"tokens": 544, "bucket": 544}),
             ("engine/decode_prep", 300, {"seqs": 3}),     # 24 rows: out
             ("engine/build_batch", 20_000, {"tokens": 9, "bucket": 32})]
    # 2 layers x 3 calls a forward; the last forward's calls must not count
    mixed = [_event(1_000 + i * 10, 2_000_000) for i in range(6)]
    small = [_event(9_000 + i * 10, 1_000_000) for i in range(6)]
    other = [_event(500, 7_000_000, kernel="_prefill_kernel")]
    ctx, logs = _ctx()
    got = gmm_roofline_pct.read(_facts(mixed + small + other, spans),
                                {"pattern": "^_gmm_kernel$"}, ctx)
    s = dict(olmoe.shapes(_cfg()), layers=2)
    flops, nbytes = costs_moe.grouped_ffn_costs(s, 4352)
    least = max(flops / 197e12, nbytes / 819e9)
    assert got == pytest.approx(100 * least / 0.012)
    assert 0 < got < 100
    assert "1 left out" in logs[-1]


def test_reader_returns_none_without_the_kernel_or_the_counters():
    ctx, logs = _ctx()
    spans = [("engine/decode_prep", 100, {"seqs": 32})]
    args = {"pattern": "^_gmm_kernel$"}
    # a dense program: no grouped GEMM in the trace
    assert gmm_roofline_pct.read(
        _facts([_event(0, 5, kernel="_prefill_kernel")], spans), args,
        ctx) is None
    # a program from before the counter: nothing to pair with
    events = [_event(i, 5) for i in range(6)]
    assert gmm_roofline_pct.read(
        _facts(events, [("engine/decode_prep", 100, None)]), args,
        ctx) is None
    # calls that are no whole forwards
    assert gmm_roofline_pct.read(_facts(events[:5], spans), args,
                                 ctx) is None
    assert "do not divide" in logs[-1]
    # a dense family's shapes (no experts): nothing to read
    facts = _facts(events, spans)
    facts["shapes"] = {"layers": 2}
    assert gmm_roofline_pct.read(facts, args, ctx) is None
