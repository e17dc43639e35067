"""CPU rehearsal of the LFM2 cell at tiny sizes (control flow, counts,
correctness against the plain reference), behind the test-only entry
``run_cell(..., allow_cpu=True)``.  No number from here is a device
metric."""

import json

import pytest

from benchmark import run
from benchmark.lib import spec

CELL = "serve-lfm2-agent-closed128"
# one 128-row tile is this engine's tile: the check's 130 tokens are a
# 128-token chunk and a chunk of two rows, as the cell's 1024 + 2, so the
# first checked row reads one row of its own chunk and one of the slot's
# tail; more slots than any other cell's rehearsal has
TINY = {
    "config": {"hidden_size": 64, "intermediate_size": 96,
               "moe_intermediate_size": 32, "num_attention_heads": 4,
               "num_key_value_heads": 2, "head_dim": 16,
               "num_hidden_layers": 6,
               "layer_types": ["conv", "conv", "full_attention", "conv",
                               "conv", "conv"],
               "vocab_size": 256, "max_position_embeddings": 1024,
               "num_experts": 8, "num_experts_per_tok": 2,
               "serve": {"block_size": 16, "token_budget": 128,
                         "max_ragged_sequence_count": 12,
                         "max_context": 512, "kv_pool_blocks": 200,
                         "check_prompt_tokens": 130,
                         "check_decode_tokens": 3}},
    "traffic": {"clients": 12,
                "prompt_tokens": {"median": 60, "min": 10, "max": 300},
                "output_tokens": {"min": 4, "max": 10},
                "preroll_s": 1.0, "drain_s": 30.0, "trace_seconds": 1.0,
                "start_stagger_s": 1.0}}


@pytest.mark.parametrize("trace", [False, True])
def test_lfm2_cell_rehearses_on_cpu(trace):
    out = run.run_cell(CELL, 3_100_000_013, 2.0, trace, overrides=TINY,
                       allow_cpu=True)
    facts = out.pop("_facts")
    assert out["rehearsal"] and out["overrides"]
    assert out["correct"] is True
    assert out["attempted"] > 0 and out["failed"] == 0
    assert facts["programs_built_window"] == 0
    shapes = facts["shapes"]
    assert (shapes["experts"], shapes["router_width"]) == (8, 8)
    assert (shapes["dense_layers"], shapes["moe_layers"]) == (2, 4)
    assert (shapes["attn_layers"], shapes["conv_layers"]) == (1, 5)
    assert shapes["kv_bytes_per_token"] == 1 * 2 * 2 * 16 * 2
    assert shapes["state_bytes_per_seq"] == 5 * 2 * 64 * 2
    assert shapes["state_slots"] == 12
    json.dumps(out)                          # the line is serialisable
    b = spec.benchmark_spec()
    if not trace:
        want = {m["name"] for m in spec.metrics_for(b, "end_to_end", CELL)}
        assert want == {"total_tok_s", "tpot_p50_ms", "setup_s"}
        assert set(out["metrics"]) == want
        assert all(v["value"] > 0 for v in out["metrics"].values())
        return
    # nothing ran on a device: device metrics are left out, not zero
    for name in ("conv_ms_decode_tick", "conv_mix_ms_tick",
                 "d64_read_ms_decode_tick", "d64_walk_roofline_pct",
                 "gmm_ms_tick", "paged_attn_ms_tick", "grid_kernel_pct",
                 "device_idle_pct"):
        assert name not in out["metrics"]
    for name in ("gmm_roofline_pct", "moe_attn_read_ms_decode_tick",
                 "moe_shared_ms_decode_tick", "gdn_ms_decode_tick",
                 "mla_read_ms_tick", "chat_kv_live_pct"):
        assert name not in out["metrics"]                # not this cell's
    m = {k: v["value"] for k, v in out["metrics"].items()}
    assert m["programs_built_window"] == 0
    assert 0 < m["kv_live_pct"] <= 100
    assert 0 < m["state_live_pct"] <= 100
    assert 0 < m["bucket_fill_pct"] <= 100
    # the counters the roofline reader sums: on the spans that own them
    spans = [r for r in facts["tracer_records"] if r.get("ph") == "X"]
    built = [r["attrs"] for r in spans if r["name"] == "engine/build_batch"]
    dec = [r["attrs"] for r in spans if r["name"] == "decode"]
    assert built and dec
    assert all(a["read_blocks"] >= 1 for a in dec)
    assert all(a["chunk_tokens"] <= a["tokens"] <= 128 and
               a["chunk_seqs"] <= 12 and 1 <= a["state_slots"] <= 12
               and 0 <= a["row_blocks"] and "attn_pairs" not in a
               for a in built)
    assert any(a["row_blocks"] > 0 and a["chunk_seqs"] > 0 for a in built)
