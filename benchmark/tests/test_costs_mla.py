"""``lib/costs_mla.py`` and ``families/moonlight.py::shapes`` against values
worked out by hand, and the readers this cell brought (``mla_roofline_pct``,
``scope_ms_tick``) on hand-made events."""

import types

import pytest

from benchmark.families import moonlight
from benchmark.lib import costs, costs_mla, spec, tracing
from benchmark.readers import mla_roofline_pct, scope_ms_tick

PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def _cfg():
    return spec.load_json(spec.BENCH_DIR +
                          "/configs/moonlight-16b-a3b-serve-1chip.json")


def test_moonlight_shapes_by_hand():
    s = moonlight.shapes(_cfg())
    # W_q 2048 x 16 x 192, W_kva 2048 x 576, W_kvb 512 x 16 x 256, W_o
    attn = 6_291_456 + 1_179_648 + 2_097_152 + 4_194_304
    assert attn == 13_762_560                    # + the latent norm's 512
    expert = 3 * 2048 * 1408
    assert expert == 8_650_752 and 2 * expert == 17_301_504
    moe = attn + 512 + 4096 + 16 * expert + 2 * expert + 2048 * 64 + 64
    assert moe == 169_611_840                    # "169.6 M = 0.339 GB"
    dense = attn + 512 + 4096 + 3 * 2048 * 11264
    assert dense == 82_973_184                   # "83.0 M"
    embed_head = 2 * 40960 * 2048
    assert s["total_params"] == 12 * moe + dense + embed_head + 2048 \
        == 2_286_089_472                         # 4.57 GB in bf16
    assert (s["layers"], s["dense_layers"], s["moe_layers"]) == (13, 1, 12)
    assert (s["experts"], s["router_width"], s["experts_per_token"]) == \
        (16, 64, 6)
    # the whole model by the same count: the published 16 B
    full = dict(_cfg(), n_routed_experts=64, router_experts=64,
                num_hidden_layers=27, vocab_size=163840)
    assert moonlight.shapes(full)["total_params"] == pytest.approx(
        15.96e9, rel=2e-3)
    # the row: 576 values of content, 640 lanes in the pool
    assert s["kv_bytes_per_token"] == 13 * 1152 == 14_976
    assert s["kv_row_bytes_per_token"] == 13 * 1280 == 16_640
    assert (s["kv_heads"], s["head_dim"]) == (1, 576)
    # what a token multiplies by here: 1.5 of its 6 experts on average
    assert s["matmul_params"] == 13 * attn + 3 * 2048 * 11264 + 12 * (
        2048 * 64 + 2 * expert + 6 * 16 * expert // 64) + 2048 * 40960


def test_mla_costs_by_hand():
    s = moonlight.shapes(_cfg())
    # 64 rows holding 1,800 blocks of 128 between them
    flops, nbytes = costs_mla.decode_read_costs(s, 1800, 128)
    assert nbytes == 13 * 1800 * 128 * 1152 == 3_450_470_400
    assert flops == 13 * 1800 * 128 * 16 * (576 + 512) * 2     # 34.8 k/pair
    r = costs.roofline(flops, nbytes, 1.0, PEAKS)
    assert r["bound"] == "memory"                # 30 FLOP/B under 240
    assert r["least_s"] == pytest.approx(4.213e-3, rel=1e-3)
    # a 1,024-token chunk from position 2,048: n (start + (n + 1) / 2)
    pairs = 1024 * 2048 + 1024 * 1025 // 2
    flops, nbytes = costs_mla.prefill_read_costs(s, pairs)
    assert flops == 13 * pairs * 10_240 and nbytes == 0
    assert flops == pytest.approx(0.349e12, rel=2e-3)
    # its 3,072 context rows expanded once
    flops, nbytes = costs_mla.expand_costs(s, 3072)
    assert flops == 13 * 3072 * 512 * 4096 * 2
    assert nbytes == 13 * 3072 * (576 + 4096) * 2
    assert costs.roofline(flops, nbytes, 1.0, PEAKS)["bound"] == "compute"


# ------------------------------------------------------------------ #
# the reader, on hand-made events
# ------------------------------------------------------------------ #
def _kernel(start, dur, kernel):
    text = ('%k = bf16[64,16,512] custom-call(), custom_call_target='
            '"tpu_custom_call", frontend_attributes={kernel_metadata='
            '{"kernel":"' + kernel + '"}}')
    return tracing.DeviceEvent(device=0, name=text,
                               label=tracing.label_of(text), start=start,
                               dur=dur)


def _host(name, start, dur):
    return tracing.HostEvent("main", name, start, dur)


def _facts(device_events, host_events, spans):
    # the profiler's clock runs 1000 ns ahead of the Tracer's
    host_events = host_events + [_host("bench/clock_sync", 1000, 1)]
    recs = [{"ph": "X", "name": n, "t0_ns": t, "t1_ns": t + 1, "attrs": a}
            for n, t, a in spans]
    return {"view": tracing.TraceView(device_events, host_events),
            "shapes": moonlight.shapes(_cfg()), "tracer_records": recs,
            "capture": {"mono_sync_ns": 0}}


def _ctx(peaks=PEAKS):
    logs = []
    return types.SimpleNamespace(
        peaks=peaks, log=logs.append,
        config={"serve": {"block_size": 128}}), logs


MS = 1_000_000
DECODE = {"pattern": "^_latent_decode_kernel$", "which": "decode"}
PREFILL = {"pattern": "^_latent_prefill_kernel$", "which": "prefill"}
EXPAND = {"pattern": "^_latent_expand_kernel$", "which": "expand"}


def test_decode_roofline_sums_over_every_tick_of_the_stretch():
    host = [_host("bench/tick", 10 * MS, 10 * MS),
            _host("engine/decode_step", 11 * MS, MS),
            _host("bench/tick", 20 * MS, 10 * MS),          # mixed
            _host("bench/tick", 30 * MS, 10 * MS),
            _host("engine/decode_step", 31 * MS, MS)]
    # the walk in two pure-decode ticks, 5 ms each, and over the mixed
    # tick's one-token rows, 4 ms
    dev = [_kernel(12 * MS, 5 * MS, "_latent_decode_kernel"),
           _kernel(22 * MS, 4 * MS, "_latent_decode_kernel"),
           _kernel(32 * MS, 5 * MS, "_latent_decode_kernel"),
           _kernel(17 * MS, 2 * MS, "_gmm_kernel")]
    spans = [("decode", 12 * MS, {"read_blocks": 1800, "steps": 1}),
             ("decode", 31 * MS - 1000, {"read_blocks": 1700, "steps": 1}),
             ("engine/build_batch", 21 * MS - 1000,
              {"tokens": 1040, "attn_pairs": 5, "ctx_rows": 7,
               "row_blocks": 1500}),
             # consumed after the stretch
             ("decode", 50 * MS, {"read_blocks": 9000, "steps": 1})]
    ctx, logs = _ctx()
    got = mla_roofline_pct.read(_facts(dev, host, spans), DECODE, ctx)
    least = 13 * (1800 + 1700 + 1500) * 128 * 1152 / 819e9
    assert got == pytest.approx(100 * least / 14e-3)
    assert 0 < got < 100 and "3 forwards" in logs[-1]
    # a stretch of mixed ticks alone (a loop at its prefill capacity)
    # still reads: the refusal of this PR's first check
    got = mla_roofline_pct.read(
        _facts([dev[3], dev[1]], host[2:3], spans[2:3]), DECODE, ctx)
    assert got == pytest.approx(
        100 * 13 * 1500 * 128 * 1152 / 819e9 / 4e-3)


def test_scope_ms_tick_divides_by_every_tick():
    host = [_host("bench/tick", 10 * MS, 10 * MS),
            _host("bench/tick", 20 * MS, 10 * MS)]
    read = "jit(step)/layers_3/attn/latent_read/dot_general"
    facts = _facts([_kernel(12 * MS, 5 * MS, "_latent_decode_kernel")],
                   host, [])
    facts["_scope_events"] = [
        (0, 11 * MS, 12 * MS, read, "fusion"),
        (0, 12 * MS, 17 * MS, read, "custom-call"),
        (0, 22 * MS, 24 * MS, read, "custom-call"),
        (0, 25 * MS, 26 * MS, "jit(step)/layers_3/moe/router/dot", "f")]
    ctx, logs = _ctx()
    args = {"scope": "/attn/latent_read/"}
    assert scope_ms_tick.read(facts, args, ctx) == pytest.approx(4.0)
    assert "2 ticks" in logs[-1]
    # a program without the scope, a stretch without a tick, no view
    assert scope_ms_tick.read(facts, {"scope": "/attn/dense_read/"},
                              ctx) is None
    none = _facts([_kernel(12 * MS, MS, "_gmm_kernel")], [], [])
    none["_scope_events"] = facts["_scope_events"]
    assert scope_ms_tick.read(none, args, ctx) is None
    facts["view"] = None
    assert scope_ms_tick.read(facts, args, ctx) is None


def test_prefill_and_expand_rooflines_read_the_build_batch_counters():
    dev = [_kernel(5 * MS, 4 * MS, "_latent_prefill_kernel"),
           _kernel(3 * MS, 2 * MS, "_latent_expand_kernel"),
           _kernel(1 * MS, MS, "_latent_decode_kernel"),
           _kernel(11 * MS, MS, "_latent_decode_kernel")]
    pairs = 1024 * 2048 + 1024 * 1025 // 2
    spans = [("engine/build_batch", 2 * MS - 1000,
              {"tokens": 1040, "chunk_tokens": 1024, "chunk_seqs": 1,
               "attn_pairs": pairs, "ctx_rows": 3072}),
             # a batch of single-token rows: no tile, no call
             ("engine/build_batch", 10 * MS - 1000,
              {"tokens": 12, "attn_pairs": 0, "ctx_rows": 0}),
             # dispatched after the stretch
             ("engine/build_batch", 50 * MS,
              {"tokens": 900, "attn_pairs": 9 * pairs, "ctx_rows": 9000})]
    ctx, _logs = _ctx()
    facts = _facts(dev, [], spans)
    got = mla_roofline_pct.read(facts, PREFILL, ctx)
    assert got == pytest.approx(100 * 13 * pairs * 10_240 / 197e12 / 4e-3)
    assert 0 < got < 100
    got = mla_roofline_pct.read(facts, EXPAND, ctx)
    assert got == pytest.approx(
        100 * 13 * 3072 * 512 * 4096 * 2 / 197e12 / 2e-3)
    assert 0 < got < 100


def test_roofline_reader_returns_none_without_kernel_counters_or_peaks():
    host = [_host("bench/tick", 0, 10 * MS),
            _host("engine/decode_step", MS, MS)]
    dev = [_kernel(2 * MS, MS, "_latent_decode_kernel"),
           _kernel(4 * MS, MS, "_latent_prefill_kernel")]
    dec = [("decode", MS, {"read_blocks": 40})]
    ctx, _ = _ctx()
    # the XLA composition, or a program without the layer: no such call
    assert mla_roofline_pct.read(
        _facts([_kernel(0, 5, "_gmm_kernel")], host, dec), DECODE,
        ctx) is None
    # a program without the counters (the parent has no attn_pairs)
    assert mla_roofline_pct.read(
        _facts(dev, host, [("engine/build_batch", MS, {"tokens": 9})]),
        PREFILL, ctx) is None
    assert mla_roofline_pct.read(
        _facts(dev, host, [("decode", MS, None)]), DECODE, ctx) is None
    # a family without the layer
    facts = _facts(dev, host, dec)
    facts["shapes"] = {"layers": 2}
    assert mla_roofline_pct.read(facts, DECODE, ctx) is None
    # no peaks (not a TPU), no view
    assert mla_roofline_pct.read(_facts(dev, host, dec), DECODE,
                                 _ctx(None)[0]) is None
    facts = _facts(dev, host, dec)
    facts["view"] = None
    assert mla_roofline_pct.read(facts, DECODE, ctx) is None
