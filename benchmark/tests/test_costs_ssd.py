"""``lib/costs_ssd.py`` and ``families/granite_moe_hybrid.py::shapes``
against values worked out by hand."""

import pytest

from benchmark.families import granite_moe_hybrid as family
from benchmark.lib import costs, costs_ssd, spec

PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def _shapes():
    return family.shapes(spec.load_json(
        spec.BENCH_DIR + "/configs/granite-4.0-h-small-serve-1chip.json"))


def test_granite_shapes_by_hand():
    s = _shapes()
    assert (s["layers"], s["ssd_layers"], s["attn_layers"]) == (10, 9, 1)
    assert (s["ssd_heads"], s["ssd_head_dim"], s["ssd_state"]) \
        == (128, 64, 128)
    assert (s["experts"], s["router_width"], s["experts_per_token"],
            s["expert_width"]) == (18, 72, 10, 768)
    assert s["conv_channels"] == 8192 + 2 * 128 == 8448
    mamba = 4096 * (8192 + 8448 + 128) + 8192 * 4096
    attn = 2 * 4096 * 4096 + 2 * 4096 * 1024
    assert (mamba, attn) == (102_236_160, 41_943_040)
    # what a token multiplies by here: 2.5 of its 10 experts on average
    moe = 4096 * 72 + 3 * 4096 * 1536 + 10 * 18 * 3 * 4096 * 768 // 72
    assert s["matmul_params"] == 9 * mamba + attn + 10 * moe + 4096 * 25088
    # 128 decode rows: 0.38 TFLOP of matmuls, 1.9 ms at the bf16 peak
    assert round(2 * 128 * s["matmul_params"] / 1e12, 2) == 0.38
    assert s["kv_bytes_per_token"] == 4096
    assert 3072 * 128 * 4096 == 1_610_612_736            # 1.61 GB
    assert s["state_bytes_per_seq"] == 9 * (4_194_304 + 50_688) == 38_204_928
    assert s["state_slots"] == 128
    assert 129 * s["state_bytes_per_seq"] == 4_928_435_712   # 4.93 GB


def test_a_token_and_a_state_by_hand():
    s = _shapes()
    assert costs_ssd.channels(s) == 8192
    assert costs_ssd.state_bytes(s) == 128 * 8192 * 4 == 4_194_304
    # decay, input, read-out over 8192 x 128; dt x a channel; dt A and its
    # exp a head
    assert costs_ssd.token_flops(s) == 5 * 8192 * 128 + 8192 + 256 \
        == 5_251_328
    # x in, y out, dt a head, B and C
    assert costs_ssd.token_row_bytes(s) == (2 * 8192 + 128 + 256) * 4 \
        == 67_072


def test_a_decode_step_of_128_rows_is_bound_by_the_state():
    s = _shapes()
    flops, nbytes = costs_ssd.step_costs(s, 128)
    assert flops == 9 * 128 * 5_251_328 == 6_049_529_856
    assert nbytes == 9 * 128 * (2 * 4_194_304 + 67_072) == 9_740_943_360
    r = costs.roofline(flops, nbytes, 1.0, PEAKS)
    assert r["bound"] == "memory"
    assert r["least_s"] == pytest.approx(11.894e-3, rel=1e-3)
    # half the rows, half the cost: only live slots count
    assert costs_ssd.step_costs(s, 64) == (flops / 2, nbytes / 2)


def test_a_chunk_reads_its_sequences_state_once_a_batch():
    s = _shapes()
    flops, nbytes = costs_ssd.chunk_costs(s, 1024, 1)
    assert flops == 9 * 1024 * 5_251_328 == 48_396_238_848
    assert nbytes == 9 * (2 * 4_194_304 + 1024 * 67_072) == 693_633_024
    r = costs.roofline(flops, nbytes, 1.0, PEAKS)
    assert r["bound"] == "memory"
    assert r["least_s"] == pytest.approx(0.8469e-3, rel=1e-3)
    # the same tokens in four sequences' chunks: three more states in and out
    more = costs_ssd.chunk_costs(s, 1024, 4)
    assert more[0] == flops
    assert more[1] - nbytes == 9 * 3 * 2 * 4_194_304
    # the matmul form executes more than this: per 128-row tile C B^T, C s,
    # B^T (.) and a head's masked product, ~0.8 GFLOP a tile and layer
    # against 128 x 5.25 MFLOP = 0.67 required
    tile = 2 * 128 * 128 * (128 + 2 * 8192 + 128 * 64)
    assert tile > 128 * costs_ssd.token_flops(s)
