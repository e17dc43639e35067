"""CPU rehearsal of the Trinity cell at tiny sizes (control flow, counts,
correctness against the plain reference), behind the test-only entry
``run_cell(..., allow_cpu=True)``.  No number from here is a device
metric."""

import json

import pytest

from benchmark import run
from benchmark.lib import spec

CELL = "serve-trinity-mixedlen-closed32"
# a window of 40 tokens over blocks of 16 and a budget of 64: the check's
# 150 tokens are three chunks, its rows sit 110 past the window and the
# window group has released 6 blocks by then; half the callers' prompts are
# past the window and half inside it
TINY = {
    "config": {"hidden_size": 64, "intermediate_size": 96,
               "moe_intermediate_size": 32, "num_attention_heads": 4,
               "num_key_value_heads": 2, "head_dim": 16,
               "sliding_window": 40, "vocab_size": 256,
               "max_position_embeddings": 1024, "num_experts": 4,
               "router_experts": 8, "num_experts_per_tok": 2,
               "serve": {"block_size": 16, "token_budget": 64,
                         "max_ragged_sequence_count": 6,
                         "max_context": 512, "kv_pool_blocks": 120,
                         "check_prompt_tokens": 150,
                         "check_decode_tokens": 3}},
    "traffic": {"clients": 6,
                "prompt_tokens": {"median": 60, "min": 10, "max": 300},
                "output_tokens": {"min": 4, "max": 10},
                "preroll_s": 1.0, "drain_s": 30.0, "trace_seconds": 1.0,
                "start_stagger_s": 1.0}}


@pytest.mark.parametrize("trace", [False, True])
def test_trinity_cell_rehearses_on_cpu(trace):
    out = run.run_cell(CELL, 3_900_000_013, 2.0, trace, overrides=TINY,
                       allow_cpu=True)
    facts = out.pop("_facts")
    assert out["rehearsal"] and out["overrides"]
    assert out["correct"] is True
    assert out["attempted"] > 0 and out["failed"] == 0
    assert facts["programs_built_window"] == 0
    shapes = facts["shapes"]
    assert (shapes["experts"], shapes["router_width"]) == (4, 8)
    assert (shapes["dense_layers"], shapes["moe_layers"]) == (1, 4)
    assert (shapes["window_layers"], shapes["full_layers"]) == (4, 1)
    assert shapes["window"] == 40
    assert shapes["kv_bytes_per_token"] == 1 * 2 * 2 * 16 * 2
    assert shapes["kv_band_bytes_per_token"] == 4 * 2 * 2 * 16 * 2
    # ceil((40 + 64) / 16) + 1 = 8 blocks a sequence, 6 sequences
    assert shapes["win_pool_blocks"] == 30
    json.dumps(out)                          # the line is serialisable
    b = spec.benchmark_spec()
    if not trace:
        want = {m["name"] for m in spec.metrics_for(b, "end_to_end", CELL)}
        assert {"total_tok_s", "setup_s"} <= want
        assert set(out["metrics"]) == want
        assert all(v["value"] > 0 for v in out["metrics"].values())
        return
    # nothing ran on a device: device metrics are left out, not zero
    for name in ("swa_read_ms_tick", "full_read_ms_tick",
                 "attn_gate_ms_tick", "banded_walk_roofline_pct",
                 "banded_prefill_roofline_pct", "gmm_ms_tick",
                 "paged_attn_ms_tick", "device_idle_pct"):
        assert name not in out["metrics"]
    for name in ("gmm_roofline_pct", "d64_walk_roofline_pct",
                 "mla_read_ms_tick", "state_live_pct", "chat_kv_live_pct"):
        assert name not in out["metrics"]                # not this cell's
    m = {k: v["value"] for k, v in out["metrics"].items()}
    assert 0 < m["kv_live_pct"] <= 100
    assert 0 < m["win_live_pct"] <= 100
    assert 0 < m["bucket_fill_pct"] <= 100
    # the counters the roofline reader sums: on the spans that own them
    spans = [r for r in facts["tracer_records"] if r.get("ph") == "X"]
    built = [r["attrs"] for r in spans if r["name"] == "engine/build_batch"]
    prep = [r["attrs"] for r in spans if r["name"] == "engine/decode_prep"]
    assert built
    for a in built + prep:
        assert a["win_pool_blocks"] == 30
        assert 0 <= a["win_blocks_held"] <= 30
        assert a["read_blocks_win"] <= 4 * a["read_blocks"]
        assert a["read_blocks_win"] % 4 == 0
    chunks = [a for a in built if "attn_pairs" in a]
    assert chunks and all(a["attn_pairs_win"] <= a["attn_pairs"]
                          for a in chunks)
    assert any(a["attn_pairs_win"] < a["attn_pairs"] for a in chunks)
    assert any(a["read_blocks"] > 0 and "attn_pairs" in a for a in built)
