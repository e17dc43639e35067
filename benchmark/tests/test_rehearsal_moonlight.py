"""CPU rehearsal of the Moonlight cell at tiny sizes (control flow, counts,
correctness against the plain reference), behind the test-only entry
``run_cell(..., allow_cpu=True)``.  No number from here is a device
metric."""

import json

import pytest

from benchmark import run
from benchmark.lib import spec

CELL = "serve-moonlight-longdoc-closed64"
# one 128-row tile is this engine's tile: the check's 200 tokens are two
# chunks, so the second expands latents the first cached, and the decoded
# tokens take the absorbed composition
TINY = {
    "config": {"hidden_size": 64, "intermediate_size": 96,
               "moe_intermediate_size": 32, "num_attention_heads": 4,
               "num_hidden_layers": 3, "vocab_size": 256,
               "kv_lora_rank": 32, "qk_nope_head_dim": 16,
               "qk_rope_head_dim": 8, "v_head_dim": 16,
               "max_position_embeddings": 1024,
               "n_routed_experts": 4, "router_experts": 8,
               "expert_start": 2, "num_experts_per_tok": 3,
               "serve": {"block_size": 16, "token_budget": 128,
                         "max_ragged_sequence_count": 6,
                         "max_context": 512, "kv_pool_blocks": 150,
                         "check_prompt_tokens": 200,
                         "check_decode_tokens": 3}},
    "traffic": {"clients": 6,
                "prompt_tokens": {"median": 100, "min": 20, "max": 400},
                "output_tokens": {"min": 4, "max": 10},
                "preroll_s": 1.0, "drain_s": 30.0, "trace_seconds": 1.0,
                "start_stagger_s": 1.0}}


@pytest.mark.parametrize("trace", [False, True])
def test_moonlight_cell_rehearses_on_cpu(trace):
    out = run.run_cell(CELL, 3_100_000_011, 2.0, trace, overrides=TINY,
                       allow_cpu=True)
    facts = out.pop("_facts")
    assert out["rehearsal"] and out["overrides"]
    assert out["correct"] is True
    assert out["attempted"] > 0 and out["failed"] == 0
    assert facts["programs_built_window"] == 0
    shapes = facts["shapes"]
    assert (shapes["experts"], shapes["router_width"]) == (4, 8)
    assert (shapes["dense_layers"], shapes["moe_layers"]) == (1, 2)
    assert shapes["kv_bytes_per_token"] == 3 * 40 * 2
    json.dumps(out)                          # the line is serialisable
    b = spec.benchmark_spec()
    if not trace:
        want = {m["name"] for m in spec.metrics_for(b, "end_to_end", CELL)}
        assert want == {"total_tok_s", "setup_s"}      # PERF.md, PR 31
        assert set(out["metrics"]) == want
        assert all(v["value"] > 0 for v in out["metrics"].values())
        return
    # nothing ran on a device: device metrics are left out, not zero
    for name in ("mla_read_ms_tick", "mla_decode_roofline_pct",
                 "mla_prefill_ms_tick", "mla_prefill_roofline_pct",
                 "mla_expand_ms_tick", "mla_expand_roofline_pct",
                 "moe_shared_ms_decode_tick", "gmm_ms_tick",
                 "device_idle_pct"):
        assert name not in out["metrics"]
    for name in ("gmm_roofline_pct", "grid_kernel_pct",
                 "moe_attn_read_ms_decode_tick", "paged_attn_ms_tick"):
        assert name not in out["metrics"]                # not this cell's
    # (the accepted metrics that move tpot_p50_ms do not list this cell:
    # it holds no TPOT end to end, PERF.md PR 31)
    assert "programs_built_window" not in out["metrics"]
    m = {k: v["value"] for k, v in out["metrics"].items()}
    assert 0 < m["kv_live_pct"] <= 100
    assert 0 < m["bucket_fill_pct"] <= 100
    # the counters the roofline reader sums: on the spans that own them
    spans = [r for r in facts["tracer_records"] if r.get("ph") == "X"]
    built = [r["attrs"] for r in spans if r["name"] == "engine/build_batch"]
    dec = [r["attrs"] for r in spans if r["name"] == "decode"]
    assert built and dec
    assert all(a["read_blocks"] >= 1 for a in dec)
    assert all(a["chunk_tokens"] <= a["tokens"] <= 128 and
               a["chunk_seqs"] <= 6 and a["ctx_rows"] >= a["chunk_tokens"]
               and a["attn_pairs"] >= a["chunk_tokens"]
               and 0 <= a["row_blocks"] for a in built)
    assert any(a["row_blocks"] > 0 and a["chunk_seqs"] > 0 for a in built)
    assert any(a["chunk_seqs"] > 0 for a in built)
