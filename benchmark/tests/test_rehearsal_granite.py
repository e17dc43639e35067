"""CPU rehearsal of the Granite-4.0-H cell at tiny sizes (control flow,
counts, correctness against the plain reference), behind the test-only
entry ``run_cell(..., allow_cpu=True)``.  No number from here is a device
metric."""

import json

import pytest

from benchmark import run
from benchmark.lib import spec

CELL = "serve-granite4h-agent-closed128"
# one 128-row tile is this engine's tile: the check's 200 tokens are a
# 128-token chunk and one of 72, as the cell's 1024 + 512, so the Mamba-2
# state and the convolution's tail cross a chunk boundary inside ``correct``
TINY = {
    "config": {"hidden_size": 64, "intermediate_size": 32,
               "shared_intermediate_size": 48,
               "num_attention_heads": 4, "num_key_value_heads": 2,
               "num_hidden_layers": 4,
               "layer_types": ["mamba", "mamba", "attention", "mamba"],
               "mamba_n_heads": 8, "mamba_d_head": 16, "mamba_d_state": 8,
               "num_local_experts": 4, "router_experts": 8,
               "num_experts_per_tok": 2, "vocab_size": 256,
               "max_position_embeddings": 1024,
               "serve": {"block_size": 16, "token_budget": 128,
                         "max_ragged_sequence_count": 12,
                         "max_context": 512, "kv_pool_blocks": 200,
                         "check_prompt_tokens": 200,
                         "check_decode_tokens": 3}},
    "traffic": {"clients": 12,
                "prompt_tokens": {"median": 60, "min": 10, "max": 300},
                "output_tokens": {"min": 4, "max": 10},
                "preroll_s": 1.0, "drain_s": 30.0, "trace_seconds": 1.0,
                "start_stagger_s": 1.0}}


@pytest.mark.parametrize("trace", [False, True])
def test_granite_cell_rehearses_on_cpu(trace):
    out = run.run_cell(CELL, 3_100_000_059, 2.0, trace, overrides=TINY,
                       allow_cpu=True)
    facts = out.pop("_facts")
    assert out["rehearsal"] and out["overrides"]
    assert out["correct"] is True
    assert out["attempted"] > 0 and out["failed"] == 0
    assert facts["programs_built_window"] == 0
    assert facts["preemptions"] == 0
    shapes = facts["shapes"]
    assert (shapes["attn_layers"], shapes["ssd_layers"]) == (1, 3)
    assert (shapes["experts"], shapes["router_width"]) == (4, 8)
    assert shapes["kv_bytes_per_token"] == 1 * 2 * 2 * 16 * 2
    per_seq = 3 * (8 * 128 * 4 + 3 * (128 + 16) * 2)
    assert shapes["state_bytes_per_seq"] == per_seq
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
    for name in ("ssd_step_roofline_pct", "ssd_chunk_roofline_pct",
                 "ssd_step_ms_decode_tick", "ssd_chunk_ms_tick",
                 "mamba2_ms_decode_tick", "paged_attn_ms_tick",
                 "device_idle_pct", "gmm_ms_tick"):
        assert name not in out["metrics"]
    for name in ("ssm_ms_decode_tick", "conv_ms_decode_tick",
                 "gdn_ms_decode_tick", "decode_hbm_pct"):
        assert name not in out["metrics"]                # not this cell's
    m = {k: v["value"] for k, v in out["metrics"].items()}
    assert m["programs_built_window"] == 0
    assert 0 < m["kv_live_pct"] <= 100
    assert 0 < m["state_live_pct"] <= 100
    assert 0 < m["bucket_fill_pct"] <= 100
    assert "state_bytes_live_pct" not in m           # no chip, no peaks
    # the state's bytes ride the dispatch spans as Jamba's do; the pool's
    # gauge counts lane padding (a tail of 3 x 144 values holds 512 lanes)
    held = 3 * (8 * 128 * 4 + 512 * 2)
    spans = [r for r in facts["tracer_records"] if r.get("ph") == "X"]
    built = [r["attrs"] for r in spans if r["name"] == "engine/build_batch"]
    prep = [r["attrs"] for r in spans if r["name"] == "engine/decode_prep"]
    assert built and prep
    for a in built + prep:
        assert 1 <= a["state_slots"] <= 12
        assert a["state_bytes"] == a["state_slots"] * held
        assert a["state_bytes_total"] == 13 * held
    assert all(a["chunk_tokens"] <= a["tokens"] <= 128 and
               a["chunk_seqs"] <= 12 for a in built)
    assert any(a["chunk_seqs"] > 0 for a in built)
