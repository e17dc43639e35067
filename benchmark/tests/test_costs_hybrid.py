"""``lib/costs_hybrid.py`` and ``families/olmo_hybrid.py::shapes`` against
hand counts at the published Olmo-Hybrid-7B sizes cut to 8 layers (hidden
3840, 30 heads, SwiGLU 11008, 30 DeltaNet heads of 96 x 192, vocabulary
100,352), ``lib/costs_gdn.py`` at the new shape, and the
``hybrid_roofline_pct`` reader on hand-made launches."""

import types

from benchmark.families import olmo_hybrid
from benchmark.lib import costs_gdn
from benchmark.lib import costs_hybrid as ch
from benchmark.lib import costs_paged, spec
from benchmark.readers import hybrid_roofline_pct as reader

HF = spec.load_json(spec.BENCH_DIR
                    + "/configs/olmo-hybrid-7b-serve-1chip.json")
SHAPES = olmo_hybrid.shapes(HF)
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def test_parameters_by_hand():
    """ISSUE 56's count, leaf by leaf."""
    wq = 3840 * 2880
    wv = 3840 * 5760
    assert (wq, wv, 3840 * 30) == (11_059_200, 22_118_400, 115_200)
    gdn = 2 * wq + 3 * wv + 2 * 115_200 + 4 * 11_520 + 30 + 30 + 192
    assert gdn == 88_750_332
    attn = 4 * 3840 * 3840 + 2 * 3840
    assert attn == 58_990_080
    ffn = 3 * 3840 * 11008
    assert ffn == 126_812_160
    linear_layer, attn_layer = gdn + ffn + 7680, attn + ffn + 7680
    assert (linear_layer, attn_layer) == (215_570_172, 185_809_920)
    period = 3 * linear_layer + attn_layer
    assert period == 832_520_436
    head = 3840 * 100_352
    assert head == 385_351_680
    assert SHAPES["total_params"] == 2 * period + 2 * head + 3840 \
        == 2_435_748_072                                # 4.87 GB in bf16
    assert round(SHAPES["total_params"] * 2 / 1e9, 2) == 4.87
    # the whole model: eight periods
    whole = dict(HF, num_hidden_layers=32, layer_types=HF["layer_types"] * 4)
    assert olmo_hybrid.shapes(whole)["total_params"] == 8 * period \
        + 2 * head + 3840 == 7_430_870_688
    # what one token multiplies by: no norms, no convolution, no embedding
    assert SHAPES["matmul_params"] == 6 * (gdn - 46_080 - 252) \
        + 2 * (attn - 7680) + 8 * ffn + head == 2_050_037_760
    assert SHAPES["head_params"] == head
    assert (SHAPES["layers"], SHAPES["gdn_layers"], SHAPES["attn_layers"]) \
        == (8, 6, 2)


def test_state_and_keys_by_hand():
    assert costs_gdn.state_matrix_bytes(SHAPES) == 30 * 96 * 192 * 4 \
        == 2_211_840
    assert ch.conv_tail_bytes(SHAPES) == 3 * 11_520 * 2 == 69_120
    assert SHAPES["state_bytes_per_seq"] == 6 * 2_280_960 == 13_685_760
    assert 129 * SHAPES["state_bytes_per_seq"] == 1_765_463_040  # 1.77 GB
    assert SHAPES["state_slots"] == 128
    # keys: 30 KV heads x 128 x (k, v) x 2 B in each of 2 attention layers
    assert costs_paged.token_bytes_a_layer(SHAPES) == 15_360
    assert SHAPES["kv_bytes_per_token"] == 30_720
    assert 128 * SHAPES["kv_bytes_per_token"] == 3_932_160     # 3.75 MiB
    pool = HF["serve"]["kv_pool_blocks"] * 128 * 30_720
    assert round(pool / 1e9, 2) == 5.51
    # past ~450 tokens a sequence's keys outweigh its state
    assert 445 < SHAPES["state_bytes_per_seq"] / 30_720 < 446


def test_the_rule_at_the_new_shape():
    # 30 heads x (7 x 96 x 192 + 2 x 192)
    assert costs_gdn.token_flops(SHAPES) == 30 * (7 * 18_432 + 384) \
        == 3_882_240
    assert costs_gdn.token_row_bytes(SHAPES) == 30 * (192 + 384 + 2) * 4 \
        == 69_360
    flops, moved = costs_gdn.step_costs(SHAPES, 128)
    assert moved == 6 * 128 * (2 * 2_211_840 + 69_360) == 3_450_654_720
    # bandwidth bound by far: 4.2 ms at the HBM peak, 15 us of FLOPs
    assert 4.2e-3 < moved / 819e9 < 4.22e-3 and flops / 197e12 < 2e-5


def test_a_decode_tick_is_three_parts_of_like_size():
    weights = 2 * 2_050_037_760
    assert weights == 4_100_075_520                     # 5.0 ms at peak
    ctx = 122_000                                       # the traffic's mean
    tick = ch.decode_tick_bytes(SHAPES, ctx, 128)
    state = 128 * 6 * 2 * 2_280_960
    assert state == 3_503_554_560
    assert ch.state_bytes(SHAPES, 128) == state
    assert tick == weights + ctx * 30_720 + state == 11_351_470_080
    parts = (weights / tick, ctx * 30_720 / tick, state / tick)
    assert all(0.3 < p < 0.37 for p in parts)
    assert 13.8e-3 < tick / 819e9 < 13.9e-3
    # lib/costs.py counts no state: a third short
    from benchmark.lib import costs
    assert costs.decode_tick_bytes(SHAPES, weights, ctx) == tick - state


def test_a_mixed_tick_by_hand():
    # 100 one-token rows at 900 cached tokens, two chunks of 350 from 0
    tokens, seqs = 100 + 700, 102
    pairs = 2 * 350 * 351 // 2 + 100 * 901
    want = 2 * tokens * (2_050_037_760 - 385_351_680) \
        + 2 * seqs * 385_351_680 + 4 * pairs * 30 * 128 * 2 \
        + tokens * 6 * 3_882_240
    assert ch.tick_flops(SHAPES, tokens, seqs, pairs) == want
    assert 2.7e12 < want < 2.8e12                       # 14 ms at the peak
    # the rule is under a hundredth of it, attention about a quarter of that
    assert tokens * 6 * 3_882_240 < 0.01 * want


def _facts(rows, busy_ms):
    """One whole execution a row, ``busy_ms`` each, joined already."""
    execs = [{"cut": False, "busy": int(busy_ms * 1e6), "launch": {
        "launch": i + 1, "kind": kind}} for i, (kind, _a) in enumerate(rows)]
    execs[0]["cut"] = True
    records = [{"ph": "X", "name": "engine/decode_step" if kind == "decode"
                else "engine/ragged_step", "attrs": {"launch": i + 1, **a}}
               for i, (kind, a) in enumerate(rows)]
    return {"shapes": SHAPES, "tracer_records": records,
            "_launch_joined": (execs, {})}


def test_reader_on_hand_made_launches():
    ctx = types.SimpleNamespace(peaks=PEAKS, log=lambda _m: None)
    a = {"hyb_seqs": 128, "hyb_tokens": 128, "hyb_ctx_tokens": 122_000,
         "hyb_attn_pairs": 0, "hyb_state_seqs": 128}
    mixed = {"hyb_seqs": 100, "hyb_tokens": 800, "hyb_ctx_tokens": 90_100,
             "hyb_attn_pairs": 2 * 350 * 351 // 2, "hyb_state_seqs": 102}
    facts = _facts([("decode", a), ("decode", a), ("mixed", mixed),
                    ("decode", a), ("prefill", mixed)], busy_ms=20.0)
    hbm = reader.read(facts, {"kind": "decode", "what": "hbm"}, ctx)
    # 11.35 GB / 20 ms / 819 GB/s; the first execution is cut
    assert abs(hbm - 100 * 11_351_470_080 / 0.020 / 819e9) < 1e-9
    assert 69 < hbm < 69.5
    mfu = reader.read(facts, {"kind": "mixed+prefill", "what": "flops"}, ctx)
    assert abs(mfu - 100 * ch.tick_flops(
        SHAPES, 800, 102, 2 * 350 * 351 // 2 + 90_100)
        / 0.020 / 197e12) < 1e-9
    assert 69 < mfu < 71
    # a program from before the counters (the parent), a family that is no
    # hybrid this reader counts, no peaks: nothing, and no error
    bare = _facts([("decode", {}), ("decode", {})], busy_ms=20.0)
    assert reader.read(bare, {"kind": "decode", "what": "hbm"}, ctx) is None
    facts["shapes"] = {"layers": 16, "gdn_layers": 6}
    assert reader.read(facts, {"kind": "decode", "what": "hbm"}, ctx) is None
    facts["shapes"] = SHAPES
    none = types.SimpleNamespace(peaks=None, log=lambda _m: None)
    assert reader.read(facts, {"kind": "decode", "what": "hbm"}, none) is None


def test_the_proposed_entries_name_files_that_are_there():
    """The three per-layer metrics wait outside ``BENCHMARK.json`` (its cap
    of 128 entries is reached): their entries, as data, name metric files
    and readers that exist, a layer the benchmark has, an end-to-end metric
    the cell reports."""
    bench = spec.benchmark_spec()
    proposed = spec.load_json(spec.BENCH_DIR
                              + "/tools/calls/pr56_results/"
                                "per_layer_proposed.json")
    assert [m["name"] for m in proposed] == [
        "hybrid_decode_hbm_pct", "hybrid_mixed_mfu_pct",
        "mha_walk_roofline_pct"]
    layers = {m["layer"] for m in bench["per_layer"]}
    cell = "serve-olmohybrid-evalgen-closed128"
    moved = {m["name"] for m in spec.metrics_for(bench, "end_to_end", cell)}
    for m in proposed:
        f = spec.layer_metric_file(m["name"])
        spec.module("readers", f["reader"])
        assert m["layer"] in layers and m["moves"] in moved
        assert m["workloads"] == [cell] and m["unit"] == "%"
        assert m["name"] not in {p["name"] for p in bench["per_layer"]}
    assert len(bench["per_layer"]) == 128
