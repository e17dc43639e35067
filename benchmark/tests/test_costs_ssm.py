"""``lib/costs_ssm.py`` and ``families/jamba.py::shapes`` against values
worked out by hand, and the parameter count of ISSUE 46 against the tree
``serve_param_shapes`` builds."""

import math

import jax
import pytest

from benchmark.families import jamba
from benchmark.lib import costs, costs_ssm, spec

PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def _cfg():
    return spec.load_json(spec.BENCH_DIR +
                          "/configs/jamba2-3b-serve-1chip.json")


def test_jamba_shapes_by_hand():
    s = jamba.shapes(_cfg())
    mamba = 2560 * 10240 + 4 * 5120 + 5120 + 5120 * 192 + 160 * 5120 \
        + 5120 + 5120 * 16 + 5120 + 5120 * 2560 + (160 + 16 + 16)
    assert mamba == 41_241_792                          # "41.24 M"
    ffn = 3 * 2560 * 8192
    attn = 2 * 2560 * 2560 + 2 * 2560 * 128
    assert (ffn, attn) == (62_914_560, 13_762_560)      # "62.91", "13.76 M"
    mamba_layer, attn_layer = mamba + ffn + 2 * 2560, attn + ffn + 2 * 2560
    assert round(mamba_layer / 1e6, 2) == 104.16
    assert round(attn_layer / 1e6, 2) == 76.68
    embed = 65536 * 2560
    assert embed == 167_772_160
    total = 26 * mamba_layer + 2 * attn_layer + embed + 2560
    assert s["total_params"] == total
    assert round(total / 1e6) == 3029 and round(total * 2 / 1e9, 2) == 6.06
    assert (s["layers"], s["ssm_layers"], s["attn_layers"]) == (28, 26, 2)
    assert (s["ssm_channels"], s["ssm_state"]) == (5120, 16)
    # what a token multiplies by: no conv, bias, A, D or norm
    assert s["matmul_params"] == 26 * (2560 * 10240 + 5120 * 192
                                       + 160 * 5120 + 5120 * 2560) \
        + 2 * attn + 28 * ffn + embed
    # 256 decode rows: 1.55 TFLOP of matmuls, 7.9 ms at the bf16 peak
    assert round(2 * 256 * s["matmul_params"] / 1e12, 2) == 1.55
    # KV: 2 attention layers x 1 head x 128 x (k, v) x 2 bytes
    assert s["kv_bytes_per_token"] == 1024
    assert 4096 * 128 * 1024 == 536_870_912             # 0.54 GB
    # state: 16 x 5120 float32 + 3 x 5120 bf16, 26 layers
    assert s["state_bytes_per_seq"] == 26 * (327_680 + 30_720) == 9_318_400
    assert s["state_slots"] == 256
    assert 257 * s["state_bytes_per_seq"] == 2_394_828_800   # 2.39 GB


def test_param_tree_holds_the_count():
    cfg = _cfg()
    leaves = jax.tree_util.tree_leaves(jamba.serve_param_shapes(cfg))
    assert sum(math.prod(l.shape) for l in leaves) \
        == jamba.shapes(cfg)["total_params"] == 3_029_337_472


def test_scan_costs_by_hand():
    s = jamba.shapes(_cfg())
    assert costs_ssm.state_bytes(s) == 327_680
    assert costs_ssm.token_flops(s) == 7 * 81_920 + 5120 == 578_560
    assert costs_ssm.token_row_bytes(s) == (3 * 5120 + 32) * 4 == 61_568
    # a decode step of 256 sequences: every slot read and written
    flops, nbytes = costs_ssm.step_costs(s, 256)
    assert flops == 26 * 256 * 578_560
    assert nbytes == 26 * 256 * (2 * 327_680 + 61_568) == 4_771_872_768
    r = costs.roofline(flops, nbytes, 1.0, PEAKS)
    assert r["bound"] == "memory"
    assert r["least_s"] == pytest.approx(4_771_872_768 / 819e9)   # 5.8 ms
    # a batch with 640 prompt tokens of 3 sequences: the rows' bytes rule
    flops, nbytes = costs_ssm.chunk_costs(s, 640, 3)
    assert flops == 26 * 640 * 578_560
    assert nbytes == 26 * (3 * 2 * 327_680 + 640 * 61_568)
    r = costs.roofline(flops, nbytes, 1.0, PEAKS)
    assert r["bound"] == "memory"
    assert r["least_s"] == pytest.approx(nbytes / 819e9)          # 1.3 ms


def test_seeded_ssm_maps_three_leaves():
    import jax.numpy as jnp
    import numpy as np

    z = jnp.asarray([0.5, -1.0])
    tree = {"layers_0": {"mamba": {
        "A_log": jnp.zeros((4, 2)), "D": jnp.ones((2,)),
        "dt_proj": {"kernel": jnp.ones((3, 2)), "bias": z}}},
        "layers_1": {"self_attn": {"q_proj": {"kernel": jnp.ones((2, 2))}}}}
    out = jamba._seeded_ssm(tree)
    mb = out["layers_0"]["mamba"]
    np.testing.assert_allclose(np.exp(mb["A_log"][:, 1]), [1, 2, 3, 4],
                               rtol=1e-6)
    np.testing.assert_allclose(mb["D"], jamba.D_SCALE)
    np.testing.assert_allclose(mb["dt_proj"]["bias"],
                               jamba.DT_SHIFT + jamba.DT_SCALE * z)
    assert mb["dt_proj"]["kernel"] is tree["layers_0"]["mamba"][
        "dt_proj"]["kernel"]
    assert out["layers_1"] == tree["layers_1"]


# ------------------------------------------------------------------ #
# the roofline reader, on hand-made events: both sums over the same whole
# executions, whatever tick the host was in while they ran
# ------------------------------------------------------------------ #
def _kernel(start, dur, kernel):
    from benchmark.lib import tracing

    text = ('%k = f32[256,5120] custom-call(), custom_call_target='
            '"tpu_custom_call", frontend_attributes={kernel_metadata='
            '{"kernel":"' + kernel + '"}}')
    return tracing.DeviceEvent(device=0, name=text,
                               label=tracing.label_of(text), start=start,
                               dur=dur)


def _reader_facts(device_events, execs, spans):
    from benchmark.lib import tracing

    recs = [{"ph": "X", "name": n, "t0_ns": t - 10, "t1_ns": t, "attrs": a}
            for n, t, a in spans]
    return {"view": tracing.TraceView(device_events, []),
            "shapes": dict(jamba.shapes(_cfg()), ssm_layers=2),
            "tracer_records": recs, "_launch_joined": (execs, {})}


def _exec(program, start, end, d0, cut=False):
    return {"program": program, "start": start, "end": end, "busy": end
            - start, "cut": cut, "launch": {"d0": d0, "launch": 1}}


def test_roofline_reader_pairs_kernel_time_with_whole_executions():
    import types

    from benchmark.readers import ssm_roofline_pct

    ms = 1_000_000
    s = dict(jamba.shapes(_cfg()), ssm_layers=2)
    # three decode steps (the first cut by the stretch's edge) and a mixed
    # step between them; 2 layers: two calls an execution
    execs = [_exec("decode_step", 0, 10 * ms, -5 * ms, cut=True),
             _exec("decode_step", 10 * ms, 20 * ms, 2 * ms),
             _exec("ragged_step_T384_tiled", 20 * ms, 40 * ms, 12 * ms),
             _exec("decode_step", 40 * ms, 50 * ms, 22 * ms)]
    dev = [_kernel(1 * ms, 3 * ms, "_ssm_step_kernel"),      # the cut one's
           _kernel(11 * ms, 1 * ms, "_ssm_step_kernel"),
           _kernel(13 * ms, 1 * ms, "_ssm_step_kernel"),
           _kernel(21 * ms, 1 * ms, "_ssm_step_kernel"),     # mixed: no count
           _kernel(23 * ms, 4 * ms, "_ssm_chunk_kernel"),
           _kernel(28 * ms, 4 * ms, "_ssm_chunk_kernel"),
           _kernel(41 * ms, 2 * ms, "_ssm_step_kernel"),
           _kernel(44 * ms, 2 * ms, "_ssm_step_kernel")]
    spans = [("engine/decode_prep", -6 * ms, {"seqs": 256}),
             ("engine/decode_prep", 2 * ms - 1, {"seqs": 256}),
             ("engine/build_batch", 12 * ms - 1,
              {"chunk_tokens": 200, "chunk_seqs": 2}),
             ("engine/decode_prep", 22 * ms - 1, {"seqs": 250})]
    logs = []
    ctx = types.SimpleNamespace(peaks=PEAKS, log=logs.append)
    facts = _reader_facts(dev, execs, spans)
    got = ssm_roofline_pct.read(
        facts, {"pattern": "^_ssm_step_kernel$", "which": "step"}, ctx)
    least = sum(costs_ssm.step_costs(s, n)[1] for n in (256, 250)) / 819e9
    assert got == pytest.approx(100 * least / 6e-3)
    assert "2 whole executions" in logs[-1]
    got = ssm_roofline_pct.read(
        facts, {"pattern": "^_ssm_chunk_kernel$", "which": "chunk"}, ctx)
    flops, nbytes = costs_ssm.chunk_costs(s, 200, 2)
    assert got == pytest.approx(
        100 * max(flops / 197e12, nbytes / 819e9) / 8e-3)
    # no kernel of that name, no launch record, no peaks: nothing to read
    args = {"pattern": "^_ssm_step_kernel$", "which": "step"}
    assert ssm_roofline_pct.read(
        facts, {"pattern": "^_no_such_kernel$", "which": "step"}, ctx) is None
    assert ssm_roofline_pct.read(
        {**facts, "_launch_joined": (None, None)}, args, ctx) is None
    assert ssm_roofline_pct.read(
        facts, args, types.SimpleNamespace(peaks=None, log=print)) is None
