"""``lib/costs_paged.py`` and ``families/lfm2_moe.py::shapes`` against values
worked out by hand, the parameter and byte counts of the configuration
against ``serve_param_shapes``, and the reader this cell brought
(``paged_walk_roofline_pct``) on hand-made events."""

import types

import numpy as np
import pytest

from benchmark.families import lfm2_moe
from benchmark.lib import costs, costs_paged, spec, tracing
from benchmark.readers import paged_walk_roofline_pct

PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def _cfg():
    return spec.load_json(spec.BENCH_DIR +
                          "/configs/lfm2-24b-a2b-serve-1chip.json")


def test_lfm2_shapes_by_hand():
    s = lfm2_moe.shapes(_cfg())
    h = 2048
    conv = 3 * h * h + 3 * h + h * h                # W_in, taps, W_out
    assert conv == 16_783_360
    attn = 2 * h * 2048 + 2 * h * 512 + 2 * 64      # q o, k v, two norms
    assert attn == 10_485_888
    expert = 3 * h * 1536
    assert expert == 9_437_184 and 64 * expert == 603_979_776
    router, norms, dense_ffn = h * 64 + 64, 2 * h, 3 * h * 11776
    assert (router, norms, dense_ffn) == (131_136, 4_096, 72_351_744)
    dense = conv + dense_ffn + norms
    assert dense == 89_139_200                      # "89.1 M = 0.178 GB"
    moe_conv = conv + 64 * expert + router + norms
    moe_attn = attn + 64 * expert + router + norms
    assert (moe_conv, moe_attn) == (620_898_368, 614_600_896)
    embed = 65536 * h
    total = 2 * dense + 2 * moe_attn + 6 * moe_conv + embed + h
    assert s["total_params"] == total == 5_267_090_176   # 10.53 GB in bf16
    assert (s["layers"], s["attn_layers"], s["conv_layers"],
            s["dense_layers"], s["moe_layers"]) == (10, 2, 8, 2, 8)
    assert (s["experts"], s["router_width"], s["experts_per_token"],
            s["expert_width"]) == (64, 64, 4, 1536)
    # a cached token: 2 attention layers x (k + v) x 8 heads x 64 x 2 B
    assert s["kv_bytes_per_token"] == 2 * 2048 == 4_096
    # a sequence: 8 convolution layers x 2 rows x 2,048 channels x 2 B
    assert s["state_bytes_per_seq"] == 8 * 8_192 == 65_536
    assert s["state_slots"] == 128
    # what a token multiplies by: four experts of 64, the tied head once
    assert s["matmul_params"] == 8 * 4 * h * h + 2 * (attn - 128) \
        + 2 * dense_ffn + 8 * (h * 64 + 4 * expert) + embed
    # the whole model by the same count: the published 24 B / A2B
    full = dict(_cfg(), num_hidden_layers=40, layer_types=[
        "full_attention" if i % 4 == 2 else "conv" for i in range(40)])
    f = lfm2_moe.shapes(full)
    assert f["total_params"] == pytest.approx(23.84e9, rel=1e-3)
    assert f["matmul_params"] == pytest.approx(2.33e9, rel=5e-3)


def test_the_count_is_what_the_program_allocates():
    import jax

    leaves = jax.tree_util.tree_leaves(lfm2_moe.serve_param_shapes(_cfg()))
    n = sum(int(np.prod(l.shape)) for l in leaves)
    assert n == lfm2_moe.shapes(_cfg())["total_params"]
    assert 2 * n == 10_534_180_352                  # bf16 bytes: 10.53 GB
    serve = _cfg()["serve"]
    pool = serve["kv_pool_blocks"] * serve["block_size"] * 4_096
    assert pool == 1_610_612_736                    # 1.61 GB
    slots = (serve["max_ragged_sequence_count"] + 1) * 65_536
    assert slots == 8_454_144                       # 8.5 MB


def test_paged_read_costs_by_hand():
    s = lfm2_moe.shapes(_cfg())
    assert costs_paged.kv_layers(s) == 2
    assert costs_paged.token_bytes_a_layer(s) == 2_048
    # 128 rows holding 2,200 blocks of 128 between them
    flops, nbytes = costs_paged.decode_read_costs(s, 2200, 128)
    assert nbytes == 2 * 2200 * 128 * 2048 == 1_153_433_600
    assert flops == 2 * 2200 * 128 * 32 * 64 * 4    # 8,192 FLOP a key
    r = costs.roofline(flops, nbytes, 1.0, PEAKS)
    assert r["bound"] == "memory"                   # 4 FLOP/B under 240
    assert r["least_s"] == pytest.approx(1.408e-3, rel=1e-3)
    # a family whose every layer keeps keys and values has no attn_layers
    mistral = {"layers": 16, "q_heads": 32, "kv_heads": 8, "head_dim": 128}
    _, nbytes = costs_paged.decode_read_costs(mistral, 10, 128)
    assert nbytes == 16 * 10 * 128 * 4096


# ------------------------------------------------------------------ #
# the reader, on hand-made events
# ------------------------------------------------------------------ #
def _kernel(start, dur, kernel):
    text = ('%k = bf16[128,4,8,128] custom-call(), custom_call_target='
            '"tpu_custom_call", frontend_attributes={kernel_metadata='
            '{"kernel":"' + kernel + '"}}')
    return tracing.DeviceEvent(device=0, name=text,
                               label=tracing.label_of(text), start=start,
                               dur=dur)


def _host(name, start, dur):
    return tracing.HostEvent("main", name, start, dur)


def _facts(device_events, host_events, spans, shapes=None):
    # the profiler's clock runs 1000 ns ahead of the Tracer's
    host_events = host_events + [_host("bench/clock_sync", 1000, 1)]
    recs = [{"ph": "X", "name": n, "t0_ns": t, "t1_ns": t + 1, "attrs": a}
            for n, t, a in spans]
    return {"view": tracing.TraceView(device_events, host_events),
            "shapes": lfm2_moe.shapes(_cfg()) if shapes is None else shapes,
            "tracer_records": recs, "capture": {"mono_sync_ns": 0}}


def _ctx(peaks=PEAKS):
    logs = []
    return types.SimpleNamespace(
        peaks=peaks, log=logs.append,
        config={"serve": {"block_size": 128}}), logs


MS = 1_000_000
WALK = {"pattern": "^_decode_kernel$"}


def test_walk_roofline_sums_over_every_tick_of_the_stretch():
    host = [_host("bench/tick", 10 * MS, 10 * MS),
            _host("engine/decode_step", 11 * MS, MS),
            _host("bench/tick", 20 * MS, 10 * MS),          # mixed
            _host("bench/tick", 30 * MS, 10 * MS),
            _host("engine/decode_step", 31 * MS, MS)]
    # the walk in two pure-decode ticks, 2 ms each, and over the mixed
    # tick's one-token rows, 1.8 ms; the tiled kernel is another kernel
    dev = [_kernel(12 * MS, 2 * MS, "_decode_kernel"),
           _kernel(22 * MS, 1_800_000, "_decode_kernel"),
           _kernel(32 * MS, 2 * MS, "_decode_kernel"),
           _kernel(24 * MS, 3 * MS, "_prefill_kernel"),
           _kernel(17 * MS, 2 * MS, "_gmm_kernel")]
    spans = [("decode", 12 * MS, {"read_blocks": 2200, "steps": 1}),
             ("decode", 31 * MS - 1000, {"read_blocks": 2210, "steps": 1}),
             ("engine/build_batch", 21 * MS - 1000,
              {"tokens": 1100, "chunk_tokens": 1000, "chunk_seqs": 1,
               "state_slots": 128, "row_blocks": 2100}),
             # consumed after the stretch
             ("decode", 50 * MS, {"read_blocks": 9000, "steps": 1})]
    ctx, logs = _ctx()
    got = paged_walk_roofline_pct.read(_facts(dev, host, spans), WALK, ctx)
    least = 2 * (2200 + 2210 + 2100) * 128 * 2048 / 819e9
    assert got == pytest.approx(100 * least / 5.8e-3)
    assert 0 < got < 100 and "3 forwards" in logs[-1]
    # a stretch of mixed ticks alone still reads
    got = paged_walk_roofline_pct.read(
        _facts([dev[4], dev[1], dev[3]], host[2:3], spans[2:3]), WALK, ctx)
    assert got == pytest.approx(100 * (2 * 2100 * 128 * 2048 / 819e9)
                                / 1.8e-3)


def test_walk_roofline_reads_nothing_where_nothing_is_to_read():
    host = [_host("bench/tick", 10 * MS, 10 * MS)]
    dev = [_kernel(12 * MS, 2 * MS, "_decode_kernel")]
    spans = [("decode", 12 * MS, {"read_blocks": 2200, "steps": 1})]
    ctx, _ = _ctx()
    # no call of the kernel (the XLA composition, a tree before the walk)
    assert paged_walk_roofline_pct.read(
        _facts([_kernel(12 * MS, MS, "_gmm_kernel")], host, spans),
        WALK, ctx) is None
    # a program that records no counter
    assert paged_walk_roofline_pct.read(
        _facts(dev, host, [("decode", 12 * MS, {"steps": 1})]),
        WALK, ctx) is None
    # no peaks for the device, no shapes, no trace
    assert paged_walk_roofline_pct.read(
        _facts(dev, host, spans), WALK, _ctx(peaks=None)[0]) is None
    assert paged_walk_roofline_pct.read(
        _facts(dev, host, spans, shapes={}), WALK, ctx) is None
    assert paged_walk_roofline_pct.read({"view": None}, WALK, ctx) is None
