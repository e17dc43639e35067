"""CPU rehearsal of the Jamba2 cell at tiny sizes (control flow, counts,
correctness against the plain reference), behind the test-only entry
``run_cell(..., allow_cpu=True)``.  No number from here is a device
metric."""

import json
import types

import pytest

from benchmark import run
from benchmark.lib import spec
from benchmark.readers import tick_weighted_attr_peak_pct

CELL = "serve-jamba2-reason-closed256"
# one 128-row tile is this engine's tile: the check's 200 tokens are a
# 128-token chunk and one of 72, as the cell's 1024 + 512, so the scan state
# and the convolution's tail cross a chunk boundary inside ``correct``
TINY = {
    "config": {"hidden_size": 64, "intermediate_size": 96,
               "num_attention_heads": 4, "num_key_value_heads": 1,
               "num_hidden_layers": 4, "attn_layer_period": 4,
               "attn_layer_offset": 2, "mamba_d_state": 4,
               "mamba_dt_rank": 8, "vocab_size": 256,
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
def test_jamba_cell_rehearses_on_cpu(trace):
    out = run.run_cell(CELL, 3_100_000_019, 2.0, trace, overrides=TINY,
                       allow_cpu=True)
    facts = out.pop("_facts")
    assert out["rehearsal"] and out["overrides"]
    assert out["correct"] is True
    assert out["attempted"] > 0 and out["failed"] == 0
    assert facts["programs_built_window"] == 0
    assert facts["preemptions"] == 0
    shapes = facts["shapes"]
    assert (shapes["attn_layers"], shapes["ssm_layers"]) == (1, 3)
    assert shapes["kv_bytes_per_token"] == 1 * 2 * 1 * 16 * 2
    per_seq = 3 * (4 * 128 * 4 + 3 * 128 * 2)
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
    for name in ("ssm_step_roofline_pct", "ssm_chunk_roofline_pct",
                 "ssm_ms_decode_tick", "ssm_scan_ms_decode_tick",
                 "ssm_chunk_ms_tick", "paged_attn_ms_tick",
                 "device_idle_pct"):
        assert name not in out["metrics"]
    for name in ("gdn_ms_decode_tick", "conv_ms_decode_tick",
                 "gmm_ms_tick", "decode_hbm_pct", "loop_decode_hbm_pct"):
        assert name not in out["metrics"]                # not this cell's
    m = {k: v["value"] for k, v in out["metrics"].items()}
    assert m["programs_built_window"] == 0
    assert 0 < m["kv_live_pct"] <= 100
    assert 0 < m["state_live_pct"] <= 100
    assert 0 < m["bucket_fill_pct"] <= 100
    # bytes held over the chip's HBM: no chip here, so no peaks and no line;
    # the reader itself, handed the v5e's
    assert "state_bytes_live_pct" not in m
    ctx = types.SimpleNamespace(peaks={"hbm_bytes": 16e9}, log=print)
    got = tick_weighted_attr_peak_pct.read(
        facts, {"attr": "state_bytes", "peak": "hbm_bytes"}, ctx)
    assert got == pytest.approx(
        m["state_live_pct"] / 100 * 12 * per_seq / 16e9 * 100, rel=1e-6)
    assert tick_weighted_attr_peak_pct.read(
        facts, {"attr": "no_such_counter", "peak": "hbm_bytes"}, ctx) is None
    # the counters the roofline reader sums: on the spans that own them
    spans = [r for r in facts["tracer_records"] if r.get("ph") == "X"]
    built = [r["attrs"] for r in spans if r["name"] == "engine/build_batch"]
    prep = [r["attrs"] for r in spans if r["name"] == "engine/decode_prep"]
    assert built and prep
    for a in built + prep:
        assert 1 <= a["state_slots"] <= 12
        assert a["state_bytes"] == a["state_slots"] * per_seq
        assert a["state_bytes_total"] == 13 * per_seq
    assert all(a["chunk_tokens"] <= a["tokens"] <= 128 and
               a["chunk_seqs"] <= 12 for a in built)
    assert any(a["chunk_seqs"] > 0 for a in built)
