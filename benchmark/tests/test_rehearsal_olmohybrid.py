"""CPU rehearsal of the Olmo-Hybrid cell at tiny sizes (control flow, counts,
correctness against the plain reference), behind the test-only entry
``run_cell(..., allow_cpu=True)``.  No number from here is a device
metric."""

import json
import types

import pytest

from benchmark import run
from benchmark.lib import spec
from benchmark.readers import hybrid_roofline_pct

CELL = "serve-olmohybrid-evalgen-closed128"
# the published SHAPE CLASS: 6 heads (no multiple of 4 or 8) of 24 keys x
# 48 values, two periods of 3 linear : 1 full.  One 128-row tile is this
# engine's tile: the check's 200 tokens are a 128-token chunk and one of
# 72, as the cell's 1024 + 512, so the state and the convolution's tail
# cross a chunk boundary inside ``correct``
KINDS = ["linear_attention"] * 3 + ["full_attention"]
TINY = {
    "config": {"hidden_size": 96, "intermediate_size": 160,
               "num_attention_heads": 6, "num_key_value_heads": 6,
               "num_hidden_layers": 8, "layer_types": KINDS * 2,
               "linear_num_key_heads": 6, "linear_num_value_heads": 6,
               "linear_key_head_dim": 24, "linear_value_head_dim": 48,
               "vocab_size": 512, "max_position_embeddings": 1024,
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
def test_olmo_hybrid_cell_rehearses_on_cpu(trace):
    out = run.run_cell(CELL, 3_100_000_056, 2.0, trace, overrides=TINY,
                       allow_cpu=True)
    facts = out.pop("_facts")
    assert out["rehearsal"] and out["overrides"]
    assert out["correct"] is True
    assert out["attempted"] > 0 and out["failed"] == 0
    assert facts["programs_built_window"] == 0
    assert facts["preemptions"] == 0
    shapes = facts["shapes"]
    assert (shapes["attn_layers"], shapes["gdn_layers"]) == (2, 6)
    assert shapes["kv_bytes_per_token"] == 2 * 2 * 6 * 16 * 2
    assert shapes["state_bytes_per_seq"] == 6 * (6 * 24 * 48 * 4
                                                 + 3 * 576 * 2)
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
    for name in ("gdn_step_roofline_pct", "gdn_chunk_roofline_pct",
                 "gdn_ms_decode_tick", "gdn_chunk_ms_tick",
                 "paged_attn_ms_tick", "device_idle_pct"):
        assert name not in out["metrics"]
    for name in ("moe_ms_decode_tick", "gmm_ms_tick", "ssm_ms_decode_tick",
                 "dense_ffn_ms_decode_tick", "decode_hbm_pct"):
        assert name not in out["metrics"]                # not this cell's
    m = {k: v["value"] for k, v in out["metrics"].items()}
    assert m["programs_built_window"] == 0
    assert 0 < m["kv_live_pct"] <= 100
    assert 0 < m["state_live_pct"] <= 100
    assert 0 < m["bucket_fill_pct"] <= 100
    # the pool's gauge counts what the chip holds: 48 lanes stored as 128
    per_seq = 6 * (6 * 24 * 128 * 4 + 3 * 640 * 2)
    spans = [r for r in facts["tracer_records"] if r.get("ph") == "X"]
    built = [r["attrs"] for r in spans if r["name"] == "engine/build_batch"]
    prep = [r["attrs"] for r in spans if r["name"] == "engine/decode_prep"]
    assert built and prep
    for a in built + prep:
        assert 1 <= a["state_slots"] <= 12
        assert a["state_bytes"] == a["state_slots"] * per_seq
        assert a["state_bytes_total"] == 13 * per_seq
    assert any(a["chunk_seqs"] > 0 for a in built)
    # the dispatch spans' own count of what each launch asked for
    launches = hybrid_roofline_pct.asked(facts)
    assert launches
    for a in launches.values():
        assert 0 <= a["hyb_seqs"] <= a["hyb_state_seqs"] <= 12
        assert a["hyb_tokens"] >= a["hyb_seqs"]
        assert a["hyb_ctx_tokens"] >= a["hyb_seqs"]
    assert any(a["hyb_attn_pairs"] > 0 for a in launches.values())
    # no device ran: the reader has no executions to join and says so
    ctx = types.SimpleNamespace(
        peaks={"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12},
        log=print)
    assert hybrid_roofline_pct.read(
        facts, {"kind": "decode", "what": "hbm"}, ctx) is None
