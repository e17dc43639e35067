"""FLOP / byte functions against values worked out by hand."""

import pytest

from benchmark.families import gpt2, mistral
from benchmark.lib import costs, spec

PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def _cfg(name):
    return spec.load_json(spec.BENCH_DIR + "/configs/" + name + ".json")


def test_mistral_shapes_by_hand():
    s = mistral.shapes(_cfg("mistral-7b-v0.1-train-z3tp-4chip"))
    # per layer: q 4096x4096, k and v 4096x1024, o 4096x4096, three MLP
    # matrices 4096x14336
    per_layer = 4096 * 4096 * 2 + 2 * 4096 * 1024 + 3 * 4096 * 14336
    assert per_layer == 218_103_808
    assert s["matmul_params"] == 6 * per_layer + 4096 * 32000
    # embedding + lm_head + 2 norms a layer + the final norm
    assert s["total_params"] == 6 * (per_layer + 8192) + 2 * 131_072_000 + 4096
    assert round(s["total_params"] / 1e6, 1) == 1570.8      # PR 21's count
    assert s["head_dim"] == 128
    s16 = mistral.shapes(_cfg("mistral-7b-v0.1-serve-1chip"))
    assert s16["kv_bytes_per_token"] == 2 * 16 * 8 * 128 * 2 == 65536


def test_gpt2_shapes_by_hand():
    cfg = _cfg("gpt2-large-train-1chip")
    s = gpt2.shapes(cfg)
    per_layer = 1280 * 3840 + 1280 * 1280 + 2 * 1280 * 5120
    assert per_layer == 19_660_800
    assert s["matmul_params"] == cfg["n_layer"] * per_layer + 1280 * 50257
    assert s["head_dim"] == 64 and s["q_heads"] == s["kv_heads"] == 20
    # biases 3840+1280+5120+1280, four LayerNorm vectors of 1280
    assert s["total_params"] == cfg["n_layer"] * (per_layer + 16640) \
        + 50257 * 1280 + 1024 * 1280 + 2560


def test_train_flops_per_token_by_hand():
    s = {"layers": 2, "q_heads": 4, "kv_heads": 2, "head_dim": 8,
         "matmul_params": 1000}
    # attention forward per token and layer: 2 matmuls x 2 FLOPs x 16/2
    # visible keys x 32 = 2 * 16 * 32 = 1024; x3 for fwd+bwd, x2 layers
    assert costs.attention_fwd_flops_per_token(s, 16) == 1024
    assert costs.train_flops_per_token(s, 16) == 6000 + 2 * 3 * 1024


def test_train_attention_step_costs_by_hand():
    s = {"layers": 1, "q_heads": 4, "kv_heads": 2, "head_dim": 8}
    flops, nbytes = costs.train_attention_step_costs(s, batch=2, seq=16)
    assert flops == 32 * 3 * 1024
    # per token: 6 x (4 + 2) heads x 8 x 2 bytes
    assert nbytes == 32 * 6 * 6 * 8 * 2


def test_roofline_says_which_bound():
    r = costs.roofline(197e12, 1.0, 2.0, PEAKS)       # 1 s of FLOPs in 2 s
    assert r["bound"] == "compute" and r["pct"] == pytest.approx(50.0)
    r = costs.roofline(1.0, 819e9, 4.0, PEAKS)        # 1 s of bytes in 4 s
    assert r["bound"] == "memory" and r["pct"] == pytest.approx(25.0)


def test_decode_tick_bytes_and_mfu():
    s = {"kv_bytes_per_token": 65536}
    assert costs.decode_tick_bytes(s, 7_500_000_000, 1000) == \
        7_500_000_000 + 65_536_000
    s = {"layers": 0, "q_heads": 1, "head_dim": 1, "matmul_params": 10 ** 9}
    # 6 GFLOP/token x 10k tokens/s = 60 TFLOP/s over 2 chips x 197
    assert costs.mfu_pct(s, 1, 1e4, 2, PEAKS) == pytest.approx(
        100 * 60e12 / (2 * 197e12))
