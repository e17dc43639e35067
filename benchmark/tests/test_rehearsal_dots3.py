"""CPU rehearsal of the dots3-note cell at tiny sizes (control flow, counts,
correctness against the plain reference), behind the test-only entry
``run_cell(..., allow_cpu=True)``.  No number from here is a device
metric."""

import json

import pytest

from benchmark import run
from benchmark.lib import spec

CELL = "serve-dots3-notes-closed48"
# the window (37) and index_topk (48) are fractions of every context: the
# check's 200 tokens are two chunks of this engine's 128-row tile, the
# second reads its band and selects among what the first cached, and the
# decoded tokens walk their bands and gather their 48 rows.  A router of 32
# outputs: at 8 one routing flip of a bf16 engine is a quarter of a row
TINY = {
    "config": {"hidden_size": 64, "intermediate_size": 96,
               "moe_intermediate_size": 32, "vocab_size": 256,
               "num_attention_heads": 4, "q_lora_rank": 48,
               "kv_lora_rank": 32, "qk_nope_head_dim": 24,
               "qk_rope_head_dim": 8, "v_head_dim": 16,
               "index_n_heads": 4, "index_topk": 48,
               "swa_num_attention_heads": 2, "swa_q_lora_rank": 40,
               "swa_kv_lora_rank": 64, "swa_qk_nope_head_dim": 40,
               "swa_qk_rope_head_dim": 8, "swa_v_head_dim": 16,
               "sliding_window_size": 37,
               "max_position_embeddings": 1024,
               "n_routed_experts": 8, "router_experts": 32,
               "expert_start": 8, "num_experts_per_tok": 4,
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
def test_dots3_cell_rehearses_on_cpu(trace):
    out = run.run_cell(CELL, 3_100_000_061, 2.0, trace, overrides=TINY,
                       allow_cpu=True)
    facts = out.pop("_facts")
    assert out["rehearsal"] and out["overrides"]
    assert out["correct"] is True
    assert out["attempted"] > 0 and out["failed"] == 0
    assert facts["programs_built_window"] == 0
    assert facts["preemptions"] == 0
    shapes = facts["shapes"]
    assert (shapes["experts"], shapes["router_width"]) == (8, 32)
    # ``layers`` counts the FULL layers (what costs_dsa multiplies by)
    assert (shapes["layers"], shapes["window_latent_layers"],
            shapes["all_layers"]) == (2, 3, 5)
    assert (shapes["dense_layers"], shapes["moe_layers"]) == (1, 4)
    # the global pool: two full layers' latent row and indexer key; the
    # window pool: three sliding layers' row, padded to a lane tile
    assert shapes["kv_bytes_per_token"] == 2 * (40 + 128) * 2
    assert shapes["kv_row_bytes_per_token"] == 2 * (128 + 128) * 2
    assert shapes["win_row_bytes_per_token"] == 3 * 128 * 2
    json.dumps(out)                          # the line is serialisable
    b = spec.benchmark_spec()
    if not trace:
        want = {m["name"] for m in spec.metrics_for(b, "end_to_end", CELL)}
        assert want == {"total_tok_s", "setup_s"}
        assert set(out["metrics"]) == want
        assert all(v["value"] > 0 for v in out["metrics"].values())
        return
    listed = {m["name"] for m in spec.metrics_for(b, "per_layer", CELL)}
    assert set(DSA) | {"win_live_pct", "attn_gate_ms_tick", "gmm_ms_tick",
                       "kv_live_pct", "closed_tpot_p50_ms"} <= listed
    assert "gmm_roofline_pct" not in listed
    assert not any(n.startswith(("mla_", "banded_")) for n in listed)
    # nothing ran on a device: device metrics are left out, not zero
    for name in DSA[:5] + ("gmm_ms_tick", "device_idle_pct",
                           "attn_gate_ms_tick"):
        assert name not in out["metrics"]
    m = {k: v["value"] for k, v in out["metrics"].items()}
    assert 0 < m["kv_live_pct"] <= 100
    assert 0 < m["win_live_pct"] <= 100
    assert 0 < m["bucket_fill_pct"] <= 100
    assert 5 < m["dsa_selected_pct"] < 70
    # the counters the readers sum: on the spans that own them
    spans = [r for r in facts["tracer_records"] if r.get("ph") == "X"]
    built = [r["attrs"] for r in spans if r["name"] == "engine/build_batch"]
    prep = [r["attrs"] for r in spans if r["name"] == "engine/decode_prep"]
    assert built and prep
    assert all(a["read_keys_win"] % 3 == 0 for a in prep + built)
    assert all(a["read_keys_win"] <= 3 * 37 * a.get("seqs", 4)
               for a in prep)
    chunks = [a for a in built if a.get("attn_pairs_win")]
    assert chunks and all(
        0 < a["attn_pairs_win"] <= a["attn_pairs"]
        and a["ctx_rows_win"] >= a["chunk_tokens"] for a in chunks)
    assert any(a.get("idx_pairs", 0) > a.get("sel_pairs", 0) > 0
               for a in built)


def test_the_waiting_metrics_read_nothing_on_the_cpu_and_do_not_raise():
    """The four per-layer metrics that wait as data
    (``tools/calls/pr61_results/per_layer_proposed.json``): their files
    parse, their readers exist, and with no device trace each returns None."""
    import os

    proposed = spec.load_json(os.path.join(
        spec.BENCH_DIR, "tools", "calls", "pr61_results",
        "per_layer_proposed.json"))
    assert [m["name"] for m in proposed] == [
        "swa_latent_read_ms_tick", "swa_latent_prefill_ms_tick",
        "swa_latent_walk_roofline_pct", "swa_latent_prefill_roofline_pct"]
    ctx = type("Ctx", (), {"peaks": None, "config": {}, "log": print})()
    for m in proposed:
        assert m["workloads"] == [CELL] and m["moves"] == "total_tok_s"
        how = spec.layer_metric_file(m["name"])
        assert set(how) <= {"what", "reader", "args"}
        reader = spec.module("readers", how["reader"])
        assert reader.read({"view": None}, how.get("args", {}), ctx) is None
