"""CPU rehearsal of the Ouro cell at tiny sizes (control flow, counts,
correctness against the plain reference), behind the test-only entry
``run_cell(..., allow_cpu=True)``.  No number from here is a device
metric."""

import json

import pytest

from benchmark import run
from benchmark.lib import spec

CELL = "serve-ouro-reason-closed8"
# 3 layers x 3 passes over blocks of 16 and a budget of 64: the check's 100
# tokens are two chunks
TINY = {
    "config": {"hidden_size": 64, "intermediate_size": 96,
               "num_hidden_layers": 3, "total_ut_steps": 3,
               "num_attention_heads": 4, "num_key_value_heads": 4,
               "head_dim": 16, "vocab_size": 256,
               "max_position_embeddings": 1024,
               "serve": {"block_size": 16, "token_budget": 64,
                         "max_ragged_sequence_count": 4,
                         "max_context": 256, "kv_pool_blocks": 40,
                         "check_prompt_tokens": 100,
                         "check_decode_tokens": 3}},
    "traffic": {"clients": 4,
                "prompt_tokens": {"median": 40, "min": 10, "max": 120},
                "output_tokens": {"min": 4, "max": 10},
                "preroll_s": 1.0, "drain_s": 30.0, "trace_seconds": 1.0,
                "start_stagger_s": 1.0}}


@pytest.mark.parametrize("trace", [False, True])
def test_ouro_cell_rehearses_on_cpu(trace):
    out = run.run_cell(CELL, 4_300_000_013, 2.0, trace, overrides=TINY,
                       allow_cpu=True)
    facts = out.pop("_facts")
    assert out["rehearsal"] and out["overrides"]
    assert out["correct"] is True
    assert out["attempted"] > 0 and out["failed"] == 0
    assert facts["programs_built_window"] == 0 and facts["preemptions"] == 0
    shapes = facts["shapes"]
    assert (shapes["layers"], shapes["passes"], shapes["cache_layers"]) \
        == (3, 3, 9)
    assert shapes["kv_bytes_per_token"] == 9 * 2 * 4 * 16 * 2
    json.dumps(out)                          # the line is serialisable
    b = spec.benchmark_spec()
    if not trace:
        want = {m["name"] for m in spec.metrics_for(b, "end_to_end", CELL)}
        assert want == {"total_tok_s", "tpot_p50_ms", "setup_s"}
        assert set(out["metrics"]) == want
        assert all(v["value"] > 0 for v in out["metrics"].values())
        return
    # nothing ran on a device: device metrics are left out, not zero
    for name in ("loop_decode_hbm_pct", "loop_mixed_mfu_pct",
                 "loop_dense_ms_decode_tick", "loop_kv_read_ms_decode_tick",
                 "loop_pass_norm_ms_tick", "paged_attn_ms_tick",
                 "device_idle_pct"):
        assert name not in out["metrics"]
    for name in ("gmm_ms_tick", "decode_hbm_pct", "win_live_pct",
                 "state_live_pct"):
        assert name not in out["metrics"]                # not this cell's
    m = {k: v["value"] for k, v in out["metrics"].items()}
    assert 0 < m["kv_live_pct"] <= 100
    assert 0 < m["bucket_fill_pct"] <= 100
    # the counters the two shares sum: on the dispatch spans
    spans = [r for r in facts["tracer_records"] if r.get("ph") == "X"]
    steps = [r["attrs"] for r in spans if r["name"] == "engine/decode_step"]
    puts = [r["attrs"] for r in spans if r["name"] == "engine/ragged_step"]
    assert steps and puts
    for a in steps + puts:
        assert (a["passes"], a["cache_layers"]) == (3, 9)
        assert a["loop_ctx_tokens"] >= a["loop_tokens"] >= a["loop_seqs"] \
            >= 1
        assert a["loop_attn_pairs"] >= a["loop_ctx_tokens"]
    assert all(a["loop_tokens"] == a["loop_seqs"] for a in steps)
    assert any(a["loop_tokens"] > a["loop_seqs"] for a in puts)
    from benchmark.readers import loop_roofline_pct
    assert len(loop_roofline_pct.asked(facts)) == len(steps) + len(puts)
