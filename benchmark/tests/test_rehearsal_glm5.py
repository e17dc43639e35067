"""CPU rehearsal of the GLM-5 cell at tiny sizes (control flow, counts,
correctness against the plain reference), behind the test-only entry
``run_cell(..., allow_cpu=True)``.  No number from here is a device
metric."""

import json

import pytest

from benchmark import run
from benchmark.lib import spec

CELL = "serve-glm5-longctx-closed16"
# index_topk 48 is a fraction of every context: the check's 200 tokens are
# two chunks of this engine's 128-row tile, the second selects among what
# the first cached, and the decoded tokens gather their 48 rows
TINY = {
    "config": {"hidden_size": 64, "intermediate_size": 96,
               "moe_intermediate_size": 32, "num_attention_heads": 4,
               "num_hidden_layers": 3, "vocab_size": 256,
               "q_lora_rank": 48, "kv_lora_rank": 32,
               "qk_nope_head_dim": 24, "qk_rope_head_dim": 8,
               "v_head_dim": 16, "index_n_heads": 4, "index_topk": 48,
               "max_position_embeddings": 1024,
               "n_routed_experts": 4, "router_experts": 8,
               "expert_start": 2, "num_experts_per_tok": 3,
               "serve": {"block_size": 16, "token_budget": 128,
                         "max_ragged_sequence_count": 4,
                         "max_context": 512, "kv_pool_blocks": 130,
                         "check_prompt_tokens": 200,
                         "check_decode_tokens": 3}},
    "traffic": {"clients": 4,
                "prompt_tokens": {"median": 150, "min": 60, "max": 400},
                "output_tokens": {"min": 4, "max": 10},
                "preroll_s": 1.0, "drain_s": 30.0, "trace_seconds": 1.0,
                "start_stagger_s": 1.0}}

DSA = ("dsa_index_ms_tick", "dsa_topk_ms_tick", "dsa_read_ms_tick",
       "dsa_index_roofline_pct", "dsa_read_roofline_pct",
       "dsa_selected_pct")


@pytest.mark.parametrize("trace", [False, True])
def test_glm5_cell_rehearses_on_cpu(trace):
    out = run.run_cell(CELL, 3_100_000_017, 2.0, trace, overrides=TINY,
                       allow_cpu=True)
    facts = out.pop("_facts")
    assert out["rehearsal"] and out["overrides"]
    assert out["correct"] is True
    assert out["attempted"] > 0 and out["failed"] == 0
    assert facts["programs_built_window"] == 0
    assert facts["preemptions"] == 0
    shapes = facts["shapes"]
    assert (shapes["experts"], shapes["router_width"]) == (4, 8)
    assert (shapes["dense_layers"], shapes["moe_layers"]) == (1, 2)
    # both pool leaves: the latent row's content and the indexer key
    assert shapes["kv_bytes_per_token"] == 3 * (40 + 128) * 2
    assert shapes["kv_row_bytes_per_token"] == 3 * (128 + 128) * 2
    json.dumps(out)                          # the line is serialisable
    b = spec.benchmark_spec()
    if not trace:
        want = {m["name"] for m in spec.metrics_for(b, "end_to_end", CELL)}
        assert want == {"total_tok_s", "setup_s"}
        assert set(out["metrics"]) == want
        assert all(v["value"] > 0 for v in out["metrics"].values())
        return
    listed = {m["name"] for m in spec.metrics_for(b, "per_layer", CELL)}
    assert set(DSA) <= listed
    assert not any(n.startswith("mla_") for n in listed)
    # nothing ran on a device: device metrics are left out, not zero
    for name in DSA[:5] + ("gmm_ms_tick", "device_idle_pct"):
        assert name not in out["metrics"]
    m = {k: v["value"] for k, v in out["metrics"].items()}
    assert 0 < m["kv_live_pct"] <= 100
    assert 0 < m["bucket_fill_pct"] <= 100
    # contexts of 60-400 against a top-k of 48: most of what is scored is
    # not read
    assert 5 < m["dsa_selected_pct"] < 70
    # the counters the readers sum: on the spans that own them
    spans = [r for r in facts["tracer_records"] if r.get("ph") == "X"]
    built = [r["attrs"] for r in spans if r["name"] == "engine/build_batch"]
    dec = [r["attrs"] for r in spans if r["name"] == "decode"]
    assert built and dec
    assert all(a["sel_keys"] <= a["idx_keys"] for a in dec + built)
    assert any(a.get("idx_pairs", 0) > a.get("sel_pairs", 0) > 0
               for a in built)
    assert all("latent_key_steps" not in a for a in built)
