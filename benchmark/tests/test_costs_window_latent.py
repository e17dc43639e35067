"""``lib/costs_window_latent.py`` against hand counts at the dots3-note
cell's sliding geometry (64 heads, rank 1,024, 192 + 64, v 128, window 513,
three layers), and the ``window_latent_roofline_pct`` reader on hand-made
spans."""

import types

from benchmark.lib import costs_window_latent as cw
from benchmark.lib import tracing
from benchmark.readers import window_latent_roofline_pct as reader

SHAPES = {"window_latent_layers": 3, "window": 513, "swa_q_heads": 64,
          "swa_kv_lora_rank": 1024, "swa_qk_nope_head_dim": 192,
          "swa_qk_rope_head_dim": 64, "swa_v_head_dim": 128}
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def test_a_band_by_hand():
    # min(t, 512) + 1 keys a row
    assert [cw.band_keys(t, 513) for t in (0, 5, 511, 512, 513, 40000)] \
        == [1, 6, 512, 513, 513, 513]
    # a chunk's rows and the 512 before its first, where there are as many
    assert cw.band_rows(0, 1024, 513) == 1024
    assert cw.band_rows(100, 1024, 513) == 1124
    assert cw.band_rows(6144, 1024, 513) == 1536
    assert cw.row_values(SHAPES) == 1088


def test_walk_costs_by_hand():
    # 48 rows past the window in three layers: 48 x 3 x 513 keys
    keys = 48 * 3 * 513
    flops, nbytes = cw.walk_costs(SHAPES, keys)
    assert nbytes == keys * 1088 * 2 == 160_745_472
    # absorbed: 64 heads x (1,088 + 1,024) x 2 FLOP a key
    assert flops == keys * 64 * 2112 * 2 == keys * 270_336
    # bound by bytes: 124 FLOP a byte against the chip's 240
    assert flops / nbytes < 197e12 / 819e9


def test_chunk_costs_by_hand():
    # one 1,024-row chunk from 6,144: every row sees 513 keys, the band
    # holds 1,536 rows
    pairs, rows = 1024 * 513, 1536
    flops, nbytes = cw.chunk_costs(SHAPES, pairs, rows)
    assert nbytes == 0.0
    pair = 64 * (192 + 64 + 128) * 2
    expand = 1024 * 64 * (192 + 128) * 2
    assert (pair, expand) == (49_152, 41_943_040)
    assert flops == 3 * (pairs * pair + rows * expand)
    # 0.0775 + 0.193 TFLOP over three layers
    assert abs(flops - 0.2707e12) < 1e9


def _facts(spans, events):
    """What the reader is handed: tracer spans on the host clock, device
    events on the profiler's, offset 0."""
    view = types.SimpleNamespace(
        device_events=events,
        window=lambda: (0, 10_000_000))
    return {"view": view, "shapes": SHAPES, "tracer_records": spans,
            "capture": {"host_offset_ns": 0}}


def test_the_reader_leaves_the_metric_out_where_nothing_is_to_read():
    ctx = types.SimpleNamespace(peaks=PEAKS, config={}, log=lambda m: None)
    args = {"which": "walk", "pattern": "^_latent_decode_kernel$"}
    # no trace at all; a configuration without window latent layers; no
    # peaks: None, never an exception
    assert reader.read({"view": None, "shapes": SHAPES}, args, ctx) is None
    assert reader.read({"view": object(), "shapes": {"layers": 5}}, args,
                       ctx) is None
    none = types.SimpleNamespace(peaks=None, config={}, log=lambda m: None)
    assert reader.read({"view": object(), "shapes": SHAPES}, args,
                       none) is None


def test_counters_and_least_time_by_hand():
    # the least time of one decode step's walk: bytes over the HBM peak
    keys = 48 * 3 * 513
    flops, nbytes = cw.walk_costs(SHAPES, keys)
    from benchmark.lib import costs
    least = costs.roofline(flops, nbytes, 1.0, PEAKS)["least_s"]
    assert abs(least - nbytes / 819e9) < 1e-12
    assert abs(least - 196.3e-6) < 1e-6
    assert reader._COUNTERS == {
        "walk": ("read_keys_win",),
        "prefill": ("attn_pairs_win", "ctx_rows_win")}
    assert tracing.total(tracing.union([(0, 5), (3, 9)])) == 9
