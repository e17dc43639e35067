"""``lib/costs_loop.py`` against hand counts at the published Ouro-2.6B sizes
(48 layers x 4 passes, hidden 2048, 16 heads of 128, SwiGLU 5632, vocabulary
49,152), ``families/ouro.py::shapes`` against the same, and the
``loop_roofline_pct`` reader on hand-made launches."""

import types

from benchmark.families import ouro
from benchmark.lib import costs_loop as cl
from benchmark.lib import spec
from benchmark.readers import loop_roofline_pct as reader

HF = spec.load_json(spec.BENCH_DIR + "/configs/ouro-2.6b-serve-1chip.json")
SHAPES = ouro.shapes(HF)
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def test_shapes_by_hand():
    # a layer: q, k, v, o of 2048 x 2048 and three of 2048 x 5632
    layer = 4 * 2048 * 2048 + 3 * 2048 * 5632
    assert layer == 51_380_224
    assert SHAPES["loop_matmul_params"] == 48 * layer == 2_466_250_752
    assert SHAPES["head_params"] == 2048 * 49152 == 100_663_296
    assert SHAPES["matmul_params"] == 4 * 48 * layer + 2048 * 49152
    assert (SHAPES["layers"], SHAPES["passes"], SHAPES["cache_layers"]) \
        == (48, 4, 192)
    # k and v of 16 heads x 128 in bf16 = 8 KB a cache layer, 1.5 MiB in all
    assert SHAPES["kv_bytes_per_token"] == 192 * 8192 == 1_572_864
    # 4 norms a layer, the final norm, the gate's weight and bias, the
    # embedding and the untied head: 2,667.97 M parameters = 5.34 GB
    assert SHAPES["total_params"] == 48 * (layer + 4 * 2048) \
        + 2 * 2048 * 49152 + 2048 + 2048 + 1 == 2_667_974_657


def test_a_decode_tick_reads_the_stack_four_times():
    weights = 2 * (4 * 2_466_250_752 + 100_663_296)
    assert weights == 19_931_332_608            # 19.9 GB: 24.3 ms at peak
    assert cl.decode_tick_bytes(SHAPES, 0) == weights
    assert cl.decode_tick_bytes(SHAPES, 2500) == weights + 2500 * 1_572_864
    # four times what "every weight once" counts of the stack
    from benchmark.lib import costs
    once = costs.decode_tick_bytes(SHAPES, 2 * SHAPES["total_params"], 0)
    assert 3.7 < weights / once < 3.75


def test_a_mixed_tick_by_hand():
    # 256 fed tokens, 8 logits rows, a 249-token chunk from position 0 and
    # 7 decode rows at 300 cached tokens
    pairs = 249 * 250 // 2 + 7 * 301
    want = 2 * 256 * 4 * 2_466_250_752 + 2 * 8 * 100_663_296 \
        + 4 * pairs * 16 * 128 * 192
    assert cl.tick_flops(SHAPES, 256, 8, pairs) == want
    assert 5.0e12 < want < 5.2e12               # 5.1 TFLOP: 26 ms at peak
    # attention is a hundredth of it at these lengths
    assert 4 * pairs * 16 * 128 * 192 < 0.011 * want


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
    a = {"loop_tokens": 8, "loop_seqs": 8, "loop_ctx_tokens": 2500,
         "loop_attn_pairs": 2500}
    mixed = {"loop_tokens": 256, "loop_seqs": 8, "loop_ctx_tokens": 2356,
             "loop_attn_pairs": 249 * 250 // 2 + 7 * 301}
    facts = _facts([("decode", a), ("decode", a), ("mixed", mixed),
                    ("decode", a), ("prefill", mixed)], busy_ms=30.0)
    hbm = reader.read(facts, {"kind": "decode", "what": "hbm"}, ctx)
    # (19.93 GB + 3.93 GB) / 30 ms / 819 GB/s; the first execution is cut
    assert abs(hbm - 100 * (19_931_332_608 + 2500 * 1_572_864)
               / 0.030 / 819e9) < 1e-9
    assert 97 < hbm < 97.2
    mfu = reader.read(facts, {"kind": "mixed+prefill", "what": "flops"}, ctx)
    assert abs(mfu - 100 * cl.tick_flops(SHAPES, 256, 8, mixed["loop_attn_pairs"])
               / 0.030 / 197e12) < 1e-9
    assert 86 < mfu < 87
    # a program without the counters (no looped stack), or no peaks
    bare = _facts([("decode", {}), ("decode", {})], busy_ms=30.0)
    assert reader.read(bare, {"kind": "decode", "what": "hbm"}, ctx) is None
    facts["shapes"] = {"layers": 16}
    assert reader.read(facts, {"kind": "decode", "what": "hbm"}, ctx) is None
    facts["shapes"] = SHAPES
    none = types.SimpleNamespace(peaks=None, log=lambda _m: None)
    assert reader.read(facts, {"kind": "decode", "what": "hbm"}, none) is None
