"""CPU rehearsal of the OLMoE cell at tiny sizes (control flow, counts,
correctness against the plain reference), behind the test-only entry
``run_cell(..., allow_cpu=True)``, and the readers this cell brought, on
the rehearsal's own records.  No number from here is a device metric."""

import json

import pytest

from benchmark import run
from benchmark.lib import spec

CELL = "serve-olmoe-chat-closed32"
TINY = {
    "config": {"hidden_size": 64, "intermediate_size": 32,
               "num_attention_heads": 4, "num_key_value_heads": 4,
               "num_hidden_layers": 2, "vocab_size": 256,
               "max_position_embeddings": 512, "num_experts": 8,
               "num_experts_per_tok": 2,
               "serve": {"block_size": 16, "token_budget": 64,
                         "max_ragged_sequence_count": 4,
                         "max_context": 256, "kv_pool_blocks": 80,
                         "check_prompt_tokens": 40,
                         "check_decode_tokens": 3}},
    "traffic": {"clients": 4,
                "prompt_tokens": {"median": 24, "min": 8, "max": 60},
                "output_tokens": {"min": 4, "max": 10},
                "preroll_s": 1.0, "drain_s": 20.0, "trace_seconds": 1.0}}


@pytest.mark.parametrize("trace", [False, True])
def test_olmoe_cell_rehearses_on_cpu(trace):
    out = run.run_cell(CELL, 2_500_000_003, 2.0, trace, overrides=TINY,
                       allow_cpu=True)
    facts = out.pop("_facts")
    assert out["rehearsal"] and out["overrides"]
    assert out["correct"] is True
    assert out["attempted"] > 0 and out["failed"] == 0
    assert facts["programs_built_window"] == 0
    assert facts["shapes"]["experts"] == 8
    assert facts["shapes"]["experts_per_token"] == 2
    json.dumps(out)                          # the line is serialisable
    b = spec.benchmark_spec()
    if not trace:
        want = {m["name"] for m in spec.metrics_for(b, "end_to_end", CELL)}
        assert want == {"total_tok_s", "tpot_p50_ms", "setup_s"}
        assert set(out["metrics"]) == want
        assert all(v["value"] > 0 for v in out["metrics"].values())
        return
    # nothing ran on a device: device metrics are left out, not zero
    for name in ("gmm_roofline_pct", "gmm_ms_tick", "moe_ms_decode_tick",
                 "moe_router_ms_decode_tick", "moe_dispatch_ms_decode_tick",
                 "moe_attn_read_ms_decode_tick", "device_idle_pct",
                 "paged_attn_ms_tick", "other_device_ms_tick"):
        assert name not in out["metrics"]
    m = {k: v["value"] for k, v in out["metrics"].items()}
    assert m["programs_built_window"] == 0
    assert 0 < m["kv_live_pct"] <= 100
    assert 0 < m["bucket_fill_pct"] <= 100
    # the forwards the roofline reader pairs kernel calls with (the rows of
    # a decode step from the ``seqs`` counter this PR gave back)
    from benchmark.readers import gmm_roofline_pct

    fwds = gmm_roofline_pct.forwards(facts)
    assert fwds and all(1 <= n <= 64 + 4 for _t, n in fwds)
    assert [t for t, _n in fwds] == sorted(t for t, _n in fwds)
