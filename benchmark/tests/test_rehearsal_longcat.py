"""CPU rehearsal of the LongCat-Flash cell at tiny sizes (control flow,
counts, correctness against the plain reference), behind the test-only entry
``run_cell(..., allow_cpu=True)``, and the configuration's own arithmetic.
No number from here is a device metric."""

import json
import os

import numpy as np
import pytest

from benchmark import run
from benchmark.families import longcat_flash as family
from benchmark.lib import spec

CELL = "serve-longcat-avturn-closed64"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
# a router of 32 outputs (24 experts of which 4 are held, 8 zero-compute)
# at top-3 and scale 1: a routing flip between a zero output and an expert
# held elsewhere weighs a twentieth of a row, as at the published widths (at
# the published scale of 6 and twelve outputs it would weigh half of one);
# the check's 200 tokens are two chunks of this engine's 128-row tile
TINY = {
    "config": {"hidden_size": 64, "ffn_hidden_size": 96,
               "expert_ffn_hidden_size": 32, "num_attention_heads": 4,
               "num_layers": 2, "vocab_size": 256,
               "q_lora_rank": 48, "kv_lora_rank": 32,
               "qk_nope_head_dim": 24, "qk_rope_head_dim": 8,
               "v_head_dim": 16, "max_position_embeddings": 1024,
               "n_routed_experts": 4, "router_experts": 24,
               "zero_expert_num": 8, "routed_scaling_factor": 1,
               "expert_start": 2, "moe_topk": 3,
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

NEW = ("scmoe_branch_ms_tick", "moe_zero_ms_decode_tick",
       "dense_ffn_ms_decode_tick", "moe_zero_slot_pct", "moe_held_row_pct")
MLA = ("mla_read_ms_tick", "mla_decode_roofline_pct", "mla_prefill_ms_tick",
       "mla_prefill_roofline_pct", "mla_expand_ms_tick",
       "mla_expand_roofline_pct")


@pytest.mark.parametrize("trace", [False, True])
def test_longcat_cell_rehearses_on_cpu(trace):
    out = run.run_cell(CELL, 3_100_000_017, 2.0, trace, overrides=TINY,
                       allow_cpu=True)
    facts = out.pop("_facts")
    assert out["rehearsal"] and out["overrides"]
    assert out["correct"] is True
    assert out["attempted"] > 0 and out["failed"] == 0
    assert facts["programs_built_window"] == 0
    assert facts["preemptions"] == 0
    shapes = facts["shapes"]
    assert (shapes["layers"], shapes["moe_layers"]) == (4, 2)
    assert (shapes["experts"], shapes["router_width"],
            shapes["zero_experts"]) == (4, 32, 8)
    # a row a sub-layer: the latent row's content, and its padded lanes
    assert shapes["kv_bytes_per_token"] == 4 * 40 * 2
    assert shapes["kv_row_bytes_per_token"] == 4 * 128 * 2
    json.dumps(out)                          # the line is serialisable
    b = spec.benchmark_spec()
    if not trace:
        want = {m["name"] for m in spec.metrics_for(b, "end_to_end", CELL)}
        assert want == {"total_tok_s", "tpot_p50_ms", "setup_s"}
        assert set(out["metrics"]) == want
        assert all(v["value"] > 0 for v in out["metrics"].values())
        return
    listed = {m["name"] for m in spec.metrics_for(b, "per_layer", CELL)}
    assert set(NEW) | set(MLA) | {
        "moe_ms_decode_tick", "moe_router_ms_decode_tick",
        "moe_dispatch_ms_decode_tick", "gmm_ms_tick"} <= listed
    assert "gmm_roofline_pct" not in listed
    assert not any(n.startswith("dsa_") for n in listed)
    # nothing ran on a device: device metrics are left out, not zero
    for name in NEW[:3] + MLA + ("gmm_ms_tick", "device_idle_pct"):
        assert name not in out["metrics"]
    m = {k: v["value"] for k, v in out["metrics"].items()}
    assert 0 < m["kv_live_pct"] <= 100
    # 8 of the router's 32 outputs are zero-compute, 4 are held here
    assert 10 < m["moe_zero_slot_pct"] < 45
    assert 3 < m["moe_held_row_pct"] < 30
    # the counters the readers sum: on the fetch that brought them
    spans = [r for r in facts["tracer_records"] if r.get("ph") == "X"]
    fetch = [r["attrs"] for r in spans if r["name"] == "fetch"]
    assert fetch and all(
        a["moe_zero_slots"] + a["moe_held_rows"] <= a["moe_slots"]
        and a["moe_slots"] % (3 * 2) == 0 for a in fetch)


def _cell_config():
    b = spec.benchmark_spec()
    return spec.config_for(b, spec.cell(b, CELL))


def test_the_configuration_holds_every_published_key_and_its_counts():
    cfg = _cell_config()
    reduced = set(cfg["reduced"])
    assert reduced == {"num_layers", "n_routed_experts", "vocab_size"}
    if os.path.exists(CATALOG):     # (the guide's catalog, where it is)
        with open(CATALOG) as f:
            rows = [json.loads(line) for line in f]
        row = next(r for r in rows if r["name"] == "LongCat-Flash-Omni")
        assert cfg["source"] == row["source_url"]
        for key, value in row["config"].items():
            if key not in reduced:
                assert cfg[key] == value, key
    assert (cfg["num_layers"], cfg["n_routed_experts"], cfg["vocab_size"],
            cfg["router_experts"]) == (4, 16, 16384, 512)
    import jax

    shapes = family.serve_param_shapes(cfg)
    n = sum(int(np.prod(l.shape)) for l in jax.tree_util.tree_leaves(shapes))
    assert n == 5_172_749_312 == family.shapes(cfg)["total_params"]
    whole = dict(cfg, num_layers=28, n_routed_experts=512, vocab_size=131072)
    assert round(family.param_counts(whole)["total"] / 1e9, 2) == 560.66
    assert family.shapes(cfg)["kv_row_bytes_per_token"] == 10_240
    sv = cfg["serve"]
    assert sv["max_context"] == 4096 and sv["kv_pool_blocks"] * \
        sv["block_size"] * 10_240 == 1_342_177_280
