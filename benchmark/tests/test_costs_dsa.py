"""``lib/costs_dsa.py`` and ``families/glm_moe_dsa.py::shapes`` against
values worked out by hand, the parameter count against the program's own
tree, and the readers this cell brought (``dsa_roofline_pct``,
``dsa_selected_pct``) on hand-made events."""

import types

import numpy as np
import pytest

from benchmark.families import glm_moe_dsa
from benchmark.lib import costs, costs_dsa, spec, tracing
from benchmark.readers import dsa_roofline_pct, dsa_selected_pct

PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def _cfg():
    return spec.load_json(spec.BENCH_DIR + "/configs/glm-5-serve-1chip.json")


def test_glm5_shapes_by_hand():
    s = glm_moe_dsa.shapes(_cfg())
    # q_a 6144 x 2048, q_b 2048 x 64 x 256, kv_a 6144 x 576, kv_b 512 x 64 x
    # 448, o 16384 x 6144, the indexer 2048 x 32 x 128 + 6144 x 128 + 6144 x 32
    indexer = 8_388_608 + 786_432 + 196_608
    assert indexer == 9_371_648
    attn = 12_582_912 + 33_554_432 + 3_538_944 + 14_680_064 \
        + 100_663_296 + indexer
    assert attn == 174_391_296                       # "174.39 M"
    norms = 2 * 6144 + 2048 + 512 + 2 * 128
    expert = 3 * 6144 * 2048
    assert expert == 37_748_736                      # "37.75 M"
    router = 6144 * 256 + 256
    moe = attn + norms + 16 * expert + expert + router
    assert moe == pytest.approx(817.7e6, rel=1e-4)   # 1.635 GB in bf16
    dense = attn + norms + 3 * 6144 * 12288
    assert dense == pytest.approx(400.9e6, rel=1e-4)
    embed_head = 2 * 19360 * 6144
    assert embed_head == pytest.approx(237.9e6, rel=1e-4)
    assert s["total_params"] == 4 * moe + dense + embed_head + 6144 \
        == 3_909_632_768                             # 7.82 GB in bf16
    assert (s["layers"], s["dense_layers"], s["moe_layers"]) == (5, 1, 4)
    assert (s["experts"], s["router_width"], s["experts_per_token"]) == \
        (16, 256, 8)
    # the whole model by the same count: the published 744 B
    full = dict(_cfg(), n_routed_experts=256, num_hidden_layers=78,
                first_k_dense_replace=3, vocab_size=154880)
    assert glm_moe_dsa.shapes(full)["total_params"] == 743_911_218_432
    # both pool rows: 576 + 128 values of content, 640 + 128 lanes
    assert s["kv_bytes_per_token"] == 5 * (1152 + 256) == 7_040
    assert s["kv_row_bytes_per_token"] == 5 * 1536 == 7_680
    # what a token multiplies by here: 0.5 of its 8 experts on average
    assert s["matmul_params"] == 5 * attn + 3 * 6144 * 12288 + 4 * (
        6144 * 256 + expert + 8 * 16 * expert // 256) + 6144 * 19360


def test_the_count_is_the_programs_own_tree():
    import jax

    cfg = _cfg()
    tree = glm_moe_dsa.serve_param_shapes(cfg)
    n = sum(int(np.prod(leaf.shape))
            for leaf in jax.tree_util.tree_leaves(tree))
    assert n == glm_moe_dsa.shapes(cfg)["total_params"] == 3_909_632_768
    assert set(cfg["reduced"]) == {
        "num_hidden_layers", "first_k_dense_replace", "n_routed_experts",
        "vocab_size", "num_nextn_predict_layers"}
    sv = cfg["serve"]
    assert sv["kv_pool_blocks"] * sv["block_size"] * 7_680 \
        == 2_013_265_920                             # the pool: 2.01 GB


def test_dsa_costs_by_hand():
    s = glm_moe_dsa.shapes(_cfg())
    # 16 one-token rows at 14,000 positions each, beside a 1,024-token chunk
    # from position 8,192
    idx_keys, sel_keys = 16 * 14_000, 16 * 2048
    idx_pairs = 1024 * 8192 + 1024 * 1025 // 2
    sel_pairs = 1024 * 2048
    flops, nbytes = costs_dsa.index_costs(s, idx_keys, idx_pairs)
    assert nbytes == 5 * idx_keys * 256 == 286_720_000
    assert flops == 5 * (idx_keys + idx_pairs) * 8192
    flops, nbytes = costs_dsa.read_costs(s, sel_keys, sel_pairs)
    assert nbytes == 5 * sel_keys * 1152 == 188_743_680
    assert flops == 5 * (sel_keys + sel_pairs) * 65_536       # 65.5 k a pair
    assert costs.roofline(flops, nbytes, 1.0, PEAKS)["bound"] == "compute"
    # one-token rows alone are bound by their bytes
    r = costs.roofline(*costs_dsa.read_costs(s, sel_keys, 0), 1.0, PEAKS)
    assert r["bound"] == "memory"
    assert costs_dsa.selected_pct(idx_keys + idx_pairs,
                                  sel_keys + sel_pairs) \
        == pytest.approx(23.3, abs=0.1)
    assert costs_dsa.selected_pct(0, 0) is None


# ------------------------------------------------------------------ #
# the readers, on hand-made events
# ------------------------------------------------------------------ #
def _host(name, start, dur):
    return tracing.HostEvent("main", name, start, dur)


def _facts(scope_events, spans):
    # the profiler's clock runs 1000 ns ahead of the Tracer's
    host = [_host("bench/clock_sync", 1000, 1)]
    dev = [tracing.DeviceEvent(device=0, name="%f = fusion()",
                               label="fusion", start=s, dur=e - s)
           for _d, s, e, _op, _l in scope_events]
    recs = [{"ph": "X", "name": n, "t0_ns": t, "t1_ns": t + 1, "attrs": a}
            for n, t, a in spans]
    return {"view": tracing.TraceView(dev, host),
            "shapes": glm_moe_dsa.shapes(_cfg()), "tracer_records": recs,
            "capture": {"mono_sync_ns": 0}, "_scope_events": scope_events,
            "t_start_ns": 0, "t_stop_ns": 10 ** 12}


def _ctx(peaks=PEAKS):
    logs = []
    return types.SimpleNamespace(peaks=peaks, log=logs.append, config={}), \
        logs


MS = 1_000_000


def test_roofline_reads_the_scopes_time_against_the_counters():
    score = "jit(step)/layers_3/attn/index_score/dot_general"
    read = "jit(step)/layers_3/attn/sparse_read/dot_general"
    events = [(0, 10 * MS, 14 * MS, score, "fusion"),
              (0, 14 * MS, 34 * MS, read, "fusion"),
              (0, 40 * MS, 41 * MS, score, "fusion"),
              (0, 41 * MS, 42 * MS, read, "fusion")]
    spans = [("engine/build_batch", 10 * MS,
              {"idx_keys": 1000, "sel_keys": 900, "idx_pairs": 9_000_000,
               "sel_pairs": 2_000_000}),
             ("decode", 39 * MS, {"idx_keys": 200_000, "sel_keys": 30_000}),
             # consumed after the stretch
             ("decode", 90 * MS, {"idx_keys": 10 ** 9, "sel_keys": 10 ** 9})]
    facts = _facts(events, spans)
    ctx, logs = _ctx()
    s = facts["shapes"]
    got = dsa_roofline_pct.read(
        facts, {"which": "index", "scope": "/attn/index_score/"}, ctx)
    least = sum(costs.roofline(*costs_dsa.index_costs(s, k, p), 1.0,
                               PEAKS)["least_s"]
                for k, p in ((1000, 9_000_000), (200_000, 0)))
    assert got == pytest.approx(100 * least / 5e-3)
    assert 0 < got < 100 and "2 forwards" in logs[-1]
    got = dsa_roofline_pct.read(
        facts, {"which": "read", "scope": "/attn/sparse_read/"}, ctx)
    least = sum(costs.roofline(*costs_dsa.read_costs(s, k, p), 1.0,
                               PEAKS)["least_s"]
                for k, p in ((900, 2_000_000), (30_000, 0)))
    assert got == pytest.approx(100 * least / 21e-3)
    # a program without the scope, without the counters, no peaks, no view
    assert dsa_roofline_pct.read(
        facts, {"which": "read", "scope": "/attn/latent_read/"}, ctx) is None
    bare = _facts(events, [("decode", 39 * MS, {"read_blocks": 7})])
    assert dsa_roofline_pct.read(
        bare, {"which": "read", "scope": "/attn/sparse_read/"}, ctx) is None
    assert dsa_roofline_pct.read(
        facts, {"which": "read", "scope": "/attn/sparse_read/"},
        _ctx(None)[0]) is None
    assert dsa_roofline_pct.read(
        {**facts, "view": None},
        {"which": "read", "scope": "/attn/sparse_read/"}, ctx) is None
    # by kernel name, where a Mosaic kernel takes the step over
    kernel = ('%k = bf16[8] custom-call(), custom_call_target='
              '"tpu_custom_call", frontend_attributes={kernel_metadata='
              '{"kernel":"_sparse_read_kernel"}}')
    facts["view"] = tracing.TraceView(
        [tracing.DeviceEvent(device=0, name=kernel,
                             label=tracing.label_of(kernel), start=10 * MS,
                             dur=42 * MS)],
        [_host("bench/clock_sync", 1000, 1)])
    got = dsa_roofline_pct.read(
        facts, {"which": "read", "pattern": "^_sparse_read_kernel$"}, ctx)
    assert got == pytest.approx(100 * least / 42e-3)


def test_selected_pct_sums_the_windows_counters():
    spans = [("engine/build_batch", 5 * MS,
              {"idx_keys": 100, "sel_keys": 50, "idx_pairs": 900,
               "sel_pairs": 150}),
             ("decode", 6 * MS, {"idx_keys": 1000, "sel_keys": 200}),
             ("decode", 7 * MS, {"read_blocks": 3})]
    facts = _facts([], spans)
    assert dsa_selected_pct.read(facts, {}, _ctx()[0]) \
        == pytest.approx(100 * 400 / 2000)
    facts["t_stop_ns"] = 5 * MS + 10            # the window ends before
    assert dsa_selected_pct.read(facts, {}, _ctx()[0]) \
        == pytest.approx(100 * 200 / 1000)
    assert dsa_selected_pct.read(_facts([], spans[2:]), {}, _ctx()[0]) is None
