"""``lib/costs_window.py`` against hand counts at the Trinity cell's widths
(48 query / 8 KV heads of 128, window 4,096, four window layers and one
global), and the ``banded_roofline_pct`` reader on hand-made spans."""

import types

from benchmark.lib import costs_window as cw
from benchmark.lib import tracing
from benchmark.readers import banded_roofline_pct as reader

SHAPES = {"q_heads": 48, "kv_heads": 8, "head_dim": 128, "window": 4096,
          "window_layers": 4, "full_layers": 1}
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def test_a_pair_and_a_token():
    assert cw.pair_flops(SHAPES) == 4 * 48 * 128 == 24_576
    # a cached token: 8 heads x 128 x (k, v) x 2 B a layer
    from benchmark.lib import costs_paged
    assert costs_paged.token_bytes_a_layer(SHAPES) == 4096


def test_pairs_by_hand():
    # four queries from position 2 see 3 + 4 + 5 + 6 keys
    assert cw.causal_pairs(2, 4) == 18
    # with a window of 4: 3, 4, 4, 4
    assert cw.banded_pairs(2, 4, 4) == 15
    # wholly inside the ramp, wholly past it
    assert cw.banded_pairs(0, 3, 10) == 1 + 2 + 3
    assert cw.banded_pairs(100, 5, 10) == 50
    # a 1,024-token chunk from 6,144: every query sees the full window
    assert cw.banded_pairs(6144, 1024, 4096) == 1024 * 4096
    assert cw.causal_pairs(6144, 1024) == sum(range(6145, 7169))
    # the first chunk: the ramp alone
    assert cw.banded_pairs(0, 1024, 4096) == cw.causal_pairs(0, 1024) \
        == 1024 * 1025 // 2
    # the chunk that crosses the window's edge at position 4,095
    assert cw.banded_pairs(3584, 1024, 4096) == sum(
        min(t + 1, 4096) for t in range(3584, 4608))


def test_band_blocks_by_hand():
    # position 6,151 sees keys 2,056 .. 6,151: blocks 16 .. 48 = 33
    assert cw.band_blocks(6151, 4096, 128) == 33
    # inside the window: every block up to its own
    assert cw.band_blocks(1000, 4096, 128) == 8
    # position 4,095 still sees key 0; 4,096 no longer, but its block 0
    # holds key 1
    assert cw.band_blocks(4095, 4096, 128) == 32
    assert cw.band_blocks(4096, 4096, 128) == 33
    assert cw.band_blocks(4096 + 127, 4096, 128) == 32


def test_walk_costs_by_hand():
    # one row at position 6,151: 49 table blocks in the global layer, 33
    # in each of four window layers = 181 block reads of 128 rows
    flops, nbytes = cw.walk_costs(SHAPES, 49, 4 * 33, 128)
    keys = (49 + 132) * 128
    assert nbytes == keys * 4096 == 94_896_128
    assert flops == keys * 24_576
    # bound by bytes: 6 FLOP a byte against the chip's 240
    assert flops / PEAKS["bf16_flops_per_s"] < nbytes / \
        PEAKS["hbm_bytes_per_s"]


def test_chunk_costs_by_hand():
    full, win = cw.causal_pairs(6144, 1024), cw.banded_pairs(6144, 1024, 4096)
    flops, nbytes = cw.chunk_costs(SHAPES, full, win)
    assert flops == 24_576 * (full + 4 * win) and nbytes == 0.0
    # a prefilled token 2k past the window: ~0.57 GFLOP of attention
    assert 0.5e9 < flops / 1024 < 0.6e9


class _View:
    def __init__(self, events):
        self.device_events = events

    def window(self):
        return 0, 10_000_000


def _event(name, start, end):
    return types.SimpleNamespace(name=name, start=start, end=end,
                                 dur=end - start, device=0)


def test_reader_on_hand_made_spans():
    kernel = 'custom-call(...), frontend_attributes={kernel_metadata={"kernel":"_decode_kernel"}}'
    view = _View([_event(kernel, 1_000_000, 2_000_000),
                  _event(kernel, 3_000_000, 4_000_000),
                  _event("fusion.1", 5_000_000, 6_000_000)])
    spans = [
        {"name": "engine/decode_prep", "ph": "X", "t0_ns": 500_000,
         "t1_ns": 600_000, "attrs": {"read_blocks": 49,
                                     "read_blocks_win": 132}},
        {"name": "engine/build_batch", "ph": "X", "t0_ns": 2_500_000,
         "t1_ns": 2_600_000, "attrs": {"read_blocks": 49,
                                       "read_blocks_win": 132,
                                       "attn_pairs": 10,
                                       "attn_pairs_win": 10}},
        # outside the stretch, and a span without the counters
        {"name": "engine/decode_prep", "ph": "X", "t0_ns": 50_000_000,
         "t1_ns": 50_100_000, "attrs": {"read_blocks": 49,
                                        "read_blocks_win": 132}},
        {"name": "engine/decode_prep", "ph": "X", "t0_ns": 700_000,
         "t1_ns": 800_000, "attrs": {"seqs": 3}}]
    facts = {"view": view, "shapes": SHAPES, "tracer_records": spans,
             "_clock_offset_ns": 0}
    ctx = types.SimpleNamespace(
        peaks=PEAKS, config={"serve": {"block_size": 128}},
        log=lambda _msg: None)
    from benchmark.readers import _host_labels
    real = _host_labels.offset_ns
    _host_labels.offset_ns = lambda _f: 0
    try:
        got = reader.read(facts, {"pattern": "^_decode_kernel$",
                                  "which": "walk"}, ctx)
        none = reader.read(facts, {"pattern": "^_prefill_kernel$",
                                   "which": "prefill"}, ctx)
        bare = reader.read({**facts, "shapes": {"q_heads": 1}},
                           {"pattern": "^_decode_kernel$", "which": "walk"},
                           ctx)
    finally:
        _host_labels.offset_ns = real
    least = 2 * 94_896_128 / 819e9
    assert abs(got - 100 * least / 2e-3) < 1e-9
    assert tracing.total([(1_000_000, 2_000_000)]) == 1_000_000
    assert none is None and bare is None
