"""CPU rehearsal of the Qwen3-Next cell at tiny sizes (control flow, counts,
correctness against the plain reference), behind the test-only entry
``run_cell(..., allow_cpu=True)``, and the readers this cell brought, on
the rehearsal's own records.  No number from here is a device metric."""

import json

import pytest

from benchmark import run
from benchmark.lib import spec

CELL = "serve-qwen3next-longchat-closed32"
# one 128-row tile is the smallest budget a state-bearing model takes: the
# check's 200 tokens are two chunks, so the state crosses a boundary
TINY = {
    "config": {"hidden_size": 64, "head_dim": 16,
               "num_attention_heads": 4, "num_key_value_heads": 2,
               "num_hidden_layers": 4, "vocab_size": 256,
               "max_position_embeddings": 1024, "rope_theta": 10000,
               "linear_num_key_heads": 2, "linear_num_value_heads": 4,
               "linear_key_head_dim": 16, "linear_value_head_dim": 16,
               "num_experts": 4, "router_experts": 8, "expert_start": 2,
               "num_experts_per_tok": 3, "moe_intermediate_size": 32,
               "shared_expert_intermediate_size": 32,
               "serve": {"block_size": 16, "token_budget": 128,
                         "max_ragged_sequence_count": 4,
                         "max_context": 512, "kv_pool_blocks": 100,
                         "check_prompt_tokens": 200,
                         "check_decode_tokens": 3}},
    "traffic": {"clients": 4,
                "prompt_tokens": {"median": 60, "min": 8, "max": 300},
                "output_tokens": {"min": 4, "max": 10},
                "preroll_s": 1.0, "drain_s": 30.0, "trace_seconds": 1.0}}


@pytest.mark.parametrize("trace", [False, True])
def test_qwen3next_cell_rehearses_on_cpu(trace):
    out = run.run_cell(CELL, 2_500_000_011, 2.0, trace, overrides=TINY,
                       allow_cpu=True)
    facts = out.pop("_facts")
    assert out["rehearsal"] and out["overrides"]
    assert out["correct"] is True
    assert out["attempted"] > 0 and out["failed"] == 0
    assert facts["programs_built_window"] == 0
    shapes = facts["shapes"]
    assert (shapes["experts"], shapes["router_width"]) == (4, 8)
    assert (shapes["gdn_layers"], shapes["attn_layers"]) == (3, 1)
    assert shapes["state_slots"] == 4
    json.dumps(out)                          # the line is serialisable
    b = spec.benchmark_spec()
    if not trace:
        want = {m["name"] for m in spec.metrics_for(b, "end_to_end", CELL)}
        assert want == {"total_tok_s", "tpot_p50_ms", "setup_s"}
        assert set(out["metrics"]) == want
        assert all(v["value"] > 0 for v in out["metrics"].values())
        return
    # nothing ran on a device: device metrics are left out, not zero
    for name in ("gdn_ms_decode_tick", "gdn_rule_ms_decode_tick",
                 "gdn_step_roofline_pct", "gdn_chunk_ms_tick",
                 "gdn_chunk_roofline_pct", "moe_shared_ms_decode_tick",
                 "gmm_ms_tick", "device_idle_pct", "paged_attn_ms_tick"):
        assert name not in out["metrics"]
    assert "gmm_roofline_pct" not in out["metrics"]      # not this cell's
    m = {k: v["value"] for k, v in out["metrics"].items()}
    assert m["programs_built_window"] == 0
    assert 0 < m["kv_live_pct"] <= 100
    assert 0 < m["state_live_pct"] <= 100
    assert 0 < m["bucket_fill_pct"] <= 100
    # the counters the roofline readers sum: on the spans that own them
    spans = [r for r in facts["tracer_records"] if r.get("ph") == "X"]
    built = [r["attrs"] for r in spans if r["name"] == "engine/build_batch"]
    prep = [r["attrs"] for r in spans if r["name"] == "engine/decode_prep"]
    assert built and prep
    assert all(1 <= a["state_slots"] <= 4 for a in built + prep)
    assert all(a["chunk_tokens"] <= a["tokens"] <= 128 and
               a["chunk_seqs"] <= 4 for a in built)
    assert any(a["chunk_seqs"] > 0 for a in built)
