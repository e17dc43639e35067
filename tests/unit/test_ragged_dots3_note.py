"""dots3-note (``model_type: dots3_note``) through the normal serving path at
a small size on the CPU: ``RaggedDots3Note`` -> ``InferenceEngineV2`` (``put``,
``decode_step``, two-segment batches, TWO pools with two row widths: the
sliding layers' latent rows in the window pool, the full layers' rows and
indexer keys in the global one) -> ``ContinuousBatchScheduler``, against the
benchmark's plain float32 reference (``benchmark/reference/dots3_note.py``:
expanded keys and values on both kinds, a mask for the window, a mask for
the top-k, no cache).

The window (37: 2 x 16 + 5) and ``index_topk`` (24) are fractions of every
context here and the prompt's chunks cross both and block edges; what makes
the model what it is is drawn away from its neutral value (norm weights
uniform in 0.5 .. 1.5, the indexer key's LayerNorm bias N(0, 0.3^2), the two
kinds' ranks all different so that each rescale factor is its own), so that
leaving it out fails.
"""

import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

_REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for _path in (_REPO, os.path.join(_REPO, "tools")):
    if _path not in sys.path:
        sys.path.insert(0, _path)

from benchmark.families import dots3_note as family          # noqa: E402
from benchmark.families import moonlight as moonlight_family  # noqa: E402
from benchmark.reference import dots3_note as reference      # noqa: E402
from benchmark.reference import moonlight as moonlight_ref   # noqa: E402
from deepspeed_tpu.inference.v2 import (                     # noqa: E402
    InferenceEngineV2, RaggedInferenceEngineConfig)
from deepspeed_tpu.inference.v2.kernels import latent_flash as lf  # noqa: E402
from deepspeed_tpu.inference.v2.model_implementations import (  # noqa: E402
    ragged_dots3_note as rd)
from deepspeed_tpu.inference.v2.ragged import CacheLayoutError  # noqa: E402
from deepspeed_tpu.observability.memory import kv_occupancy  # noqa: E402
from deepspeed_tpu.observability.tracer import Tracer        # noqa: E402
from deepspeed_tpu.serving import (ContinuousBatchScheduler,  # noqa: E402
                                   SamplingParams)
from dots3_note_faults import FAULTS, fault                  # noqa: E402

WINDOW, TOPK = 37, 24
KINDS = ["full_attention"] + ["sliding_attention"] * 3 + ["full_attention"]
HF = {"model_type": "dots3_note", "vocab_size": 256, "hidden_size": 64,
      "intermediate_size": 96, "moe_intermediate_size": 32,
      "num_hidden_layers": 5, "layer_types": KINDS,
      "num_attention_heads": 4, "kv_lora_rank": 32, "q_lora_rank": 48,
      "qk_nope_head_dim": 24, "qk_rope_head_dim": 8, "v_head_dim": 16,
      "rope_theta": 8e7, "index_n_heads": 4, "index_head_dim": 128,
      "index_topk": TOPK, "attention_gate_type": "headwise",
      "swa_num_attention_heads": 2, "swa_kv_lora_rank": 64,
      "swa_q_lora_rank": 40, "swa_qk_nope_head_dim": 40,
      "swa_qk_rope_head_dim": 8, "swa_v_head_dim": 16,
      "swa_rope_theta": 5e4, "swa_attention_gate_type": "headwise",
      "sliding_window_size": WINDOW, "apply_mla_qkv_lora_rescale": True,
      "n_routed_experts": 4, "router_experts": 8, "expert_start": 2,
      "n_shared_experts": 1, "num_experts_per_tok": 2,
      "first_k_dense_replace": 1, "moe_layer_freq": 1,
      "norm_topk_prob": True, "routed_scaling_factor": 1.0,
      "scoring_func": "sigmoid", "topk_method": "noaux_tc",
      "rms_norm_eps": 1e-5, "max_position_embeddings": 512}
#: widths the banded walk can tile: a sliding row of 128 + 8 -> 256 lanes
HF_KERNEL = dict(HF, swa_kv_lora_rank=128)
MAX_SEQS, BUDGET, TILE, BLOCK = 8, 64, 16, 16
# the same float32 mathematics in another order (measured 9e-7 here)
F32_TOL = 1e-4
# the benchmark's own limit (``LOGIT_TOL`` of ``runners/serve_ragged.py``)
BF16_TOL = 0.03


def _config(dtype, hf=HF):
    cfg = family.program_config(hf)
    cfg.dtype = dtype
    return cfg


def _params(hf=HF, seed=0):
    rng = np.random.default_rng(seed)
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        rd.param_shapes(_config(jnp.float32, hf)))
    out = []
    for path, leaf in flat:
        names = [str(getattr(p, "key", p)) for p in path]
        shape = leaf.shape
        if names[-1] == "scale":
            a = rng.uniform(0.5, 1.5, shape)
        elif names[-1] in ("bias", "e_score_correction_bias"):
            a = 0.3 * rng.standard_normal(shape)
        elif names[-1] == "embedding":
            a = rng.standard_normal(shape)
        elif names[-1] in ("w_gate", "w_up", "w_down"):
            a = rng.standard_normal(shape) * shape[1] ** -0.5
        elif "wg" in names:
            a = 2.0 * rng.standard_normal(shape) * shape[0] ** -0.5
        elif names[-2] == "gate_proj" and "self_attn" in names:
            a = 3.0 * rng.standard_normal(shape) * shape[0] ** -0.5
        else:
            a = rng.standard_normal(shape) * shape[0] ** -0.5
        out.append(jnp.asarray(a, jnp.float32))
    return jax.tree_util.tree_unflatten(treedef, out)


def _ref_params(params):
    """The reference's dict of the program's own values (the family's
    seeded-bias mapping is the benchmark's, undone here)."""
    ref = family.reference_params(params)
    for lp in ref["layers"]:
        if "bias" in lp:
            lp["bias"] = (lp["bias"] - moonlight_family.BIAS_MEAN) \
                / moonlight_family.BIAS_STD
    return ref


def _engine(params, act=jnp.float32, hf=HF, blocks=120, max_context=512,
            max_seqs=MAX_SEQS, budget=BUDGET, tile=TILE, interpret=None,
            **kv):
    model = rd.RaggedDots3Note(_config(act, hf), BLOCK)
    # True: the banded walk and the tile rows' sparse read through their
    # Mosaic kernels, interpreted
    model.interpret = interpret
    eng = InferenceEngineV2(
        model, jax.tree.map(lambda a: a.astype(act), params),
        RaggedInferenceEngineConfig.from_dict({
            "state_manager": {"max_ragged_batch_size": budget,
                              "max_ragged_sequence_count": max_seqs,
                              "max_context": max_context},
            "kv_cache": {"block_size": BLOCK, "num_blocks": blocks, **kv}}))
    eng.PREFILL_TILE = tile          # a 64-token budget of whole tiles
    return eng


def _ids(n, seed=3):
    return np.random.default_rng(seed).integers(0, HF["vocab_size"],
                                                size=(n,))


def _serve(eng, ids, n_prompt, uid=7, beside=0):
    if beside:      # another sequence first, kept live and long enough
        # that the window allocator has gone round its pool: from then on
        # the two allocators hand out different ids for the same entry
        eng.put([99], [_ids(beside, seed=11).tolist()])
    got = [np.asarray(eng.put([uid], [ids[:n_prompt].tolist()])[uid],
                      np.float32)]
    for t in ids[n_prompt:]:
        got.append(np.asarray(jax.device_get(
            eng.decode_step([uid], [int(t)])), np.float32)[0])
    eng.flush([uid] + [99] * bool(beside))
    return np.stack(got)


def _want(params, ids, n_prompt, hf=HF):
    return reference.logits_at(_ref_params(params), ids, hf,
                               rows=list(range(n_prompt - 1, len(ids))))


def _gap(got, want) -> float:
    assert got.shape == want.shape and np.isfinite(got).all()
    return float(np.max(np.abs(got - want)) / np.max(np.abs(want)))


N_PROMPT, N_ALL = 140, 146


@pytest.fixture(scope="module")
def clean():
    """One set of weights, one token sequence and the reference's logits
    for every case that compares the program with them."""
    params = _params()
    ids = _ids(N_ALL)
    return params, ids, _want(params, ids, N_PROMPT)


# ------------------------------------------------------------------ #
# (a) engine against reference: chunks that cross the window, index_topk
# and block edges, then decode through both pools
# ------------------------------------------------------------------ #
@pytest.mark.parametrize("n_prompt, tile, budget", [
    (20, TILE, BUDGET),     # inside the window and under index_topk
    (140, TILE, BUDGET),    # three chunks: bands over tiles, masks over tiles
    (140, 128, 60),         # no tiles: rows packed back to back
])
def test_f32_engine_matches_reference(n_prompt, tile, budget):
    params = _params()
    ids = _ids(n_prompt + 6)
    eng = _engine(params, tile=tile, budget=budget)
    assert (eng._prefill_tile() is None) == (tile == 128)
    assert _gap(_serve(eng, ids, n_prompt),
                _want(params, ids, n_prompt)) <= F32_TOL


def test_f32_engine_matches_reference_through_the_kernels():
    """The banded walk (and the full layers' tile read) interpreted, at a
    sliding row of whole lane tiles."""
    params = _params(HF_KERNEL)
    ids = _ids(N_ALL)
    eng = _engine(params, hf=HF_KERNEL, interpret=True)
    assert _gap(_serve(eng, ids, N_PROMPT),
                _want(params, ids, N_PROMPT, HF_KERNEL)) <= F32_TOL


@pytest.mark.parametrize("seed, topk", [(2, TOPK), (3, TOPK), (4, TOPK),
                                        (0, 1000), (1, 1000)])
def test_bf16_engine_is_the_same_model(seed, topk):
    """bf16 engine against the float32 reference on the same bf16-rounded
    weights, ``o_proj`` at 0.3 of its scale (the cell's own is 0.7 at a
    top-k of 2,048: ``benchmark/families/dots3_note.py``).  A top-k is a
    discontinuity (``test_ragged_glm_dsa.py`` says what a swap costs): of
    24 selected positions one swapped is a twenty-fourth of a full layer's
    read, and seeds 0 and 1 read 0.041 and 0.066 here with the indexer,
    0.011 and 0.011 with ``index_topk`` over the context (every position
    read: no swap), which is how they are run; seeds 2-4 read 0.015-0.024
    with it."""
    hf = dict(HF, index_topk=topk)
    params = _params(seed=seed)
    for i in range(HF["num_hidden_layers"]):
        o_proj = params[f"layers_{i}"]["self_attn"]["o_proj"]
        o_proj["kernel"] = o_proj["kernel"] * 0.3
    params = jax.tree.map(
        lambda a: a.astype(jnp.bfloat16).astype(jnp.float32), params)
    ids = _ids(N_ALL, seed=seed + 3)
    got = _serve(_engine(params, act=jnp.bfloat16, hf=hf), ids, N_PROMPT)
    assert _gap(got, _want(params, ids, N_PROMPT, hf)) <= BF16_TOL


def test_interleaved_requests_through_the_scheduler(clean):
    """Three requests of different lengths through ``submit`` / ``step``:
    every token is the one the reference's greedy continuation gives."""
    params, _ids_, _ = clean
    eng = _engine(params)
    sched = ContinuousBatchScheduler(eng, tracer=Tracer())
    prompts = [_ids(n, seed=20 + n).tolist() for n in (90, 41, 12)]
    reqs = [sched.submit(p, SamplingParams(greedy=True, max_new_tokens=4))
            for p in prompts]
    sched.run_until_idle()
    ref = _ref_params(params)
    for p, r in zip(prompts, reqs):
        seq = list(p)
        for tok in r.generated:
            want = reference.logits_at(ref, np.asarray(seq), HF,
                                       rows=[len(seq) - 1])[0]
            assert int(np.argmax(want)) == int(tok)
            seq.append(int(tok))
    sm = eng.state_manager
    assert sm.free_blocks == sm.allocator.num_blocks - 1
    assert sm.win_allocator.free_blocks == sm.win_allocator.num_blocks - 1


# ------------------------------------------------------------------ #
# (b) faults: each must fail the tolerance (dots3_note_faults.py, beside
# this file; benchmark/tools/calls/pr61_faults.py applies the same on the
# chip at the published widths)
# ------------------------------------------------------------------ #
def test_the_fault_table_is_the_issues():
    assert set(FAULTS) == {
        "window_minus_1", "window_plus_1", "gate_dropped", "gate_per_value",
        "q_rescale_dropped", "kv_rescale_dropped", "rescales_swapped",
        "rope_bases_swapped", "window_reads_global", "indexer_skipped",
        "band_released_early"}


@pytest.fixture
def no_table_check(monkeypatch):
    """The tables' debug validation off, as on the chip: what a fault in
    the tables does to the logits, not to the validation."""
    from deepspeed_tpu.inference.v2.ragged import ragged_wrapper

    monkeypatch.setattr(ragged_wrapper, "RAGGED_DEBUG", False)


@pytest.mark.parametrize("name", FAULTS)
def test_each_fault_fails_the_tolerance(name, clean, no_table_check):
    params, ids, want = clean
    with fault(name, BLOCK):
        got = _serve(_engine(params), ids, N_PROMPT, beside=450)
    assert _gap(got, want) > 100 * F32_TOL, name


@pytest.mark.parametrize("name", ["window_minus_1", "window_plus_1",
                                  "band_released_early"])
def test_the_band_faults_fail_through_the_walk_kernel_too(
        name, no_table_check):
    params = _params(HF_KERNEL)
    ids = _ids(N_ALL)
    want = _want(params, ids, N_PROMPT, HF_KERNEL)
    with fault(name, BLOCK):
        got = _serve(_engine(params, hf=HF_KERNEL, interpret=True), ids,
                     N_PROMPT)
    assert _gap(got, want) > 100 * F32_TOL, name


def test_a_band_released_early_is_caught_by_the_tables_validation(clean):
    from deepspeed_tpu.inference.v2.ragged.ragged_wrapper import (
        RAGGED_DEBUG, RaggedMetadataError)

    assert RAGGED_DEBUG         # tests/conftest.py turns it on
    params, ids, _ = clean
    with fault("band_released_early", BLOCK):
        with pytest.raises(RaggedMetadataError, match="window table"):
            _serve(_engine(params), ids, N_PROMPT)


def test_clean_is_no_fault(clean):
    params, ids, want = clean
    with fault("clean"):
        assert _gap(_serve(_engine(params), ids, N_PROMPT, beside=450),
                    want) <= F32_TOL
    with pytest.raises(ValueError, match="no fault named"):
        with fault("nonesuch"):
            pass


def test_what_the_model_cannot_be_is_refused_by_name():
    for over, match in (
            ({"attention_gate_type": "elementwise"}, "headwise"),
            ({"swa_attention_gate_type": "none"}, "headwise"),
            ({"index_head_dim": 64}, "128-lane"),
            ({"n_group": 8}, "one group"),
            ({"layer_types": KINDS[:4]}, "layer_types")):
        with pytest.raises((NotImplementedError, ValueError), match=match):
            family.program_config({**HF, **over})
    with pytest.raises(NotImplementedError, match="one chip"):
        rd.RaggedDots3Note(_config(jnp.float32), BLOCK, mesh=object())


# ------------------------------------------------------------------ #
# (c) the share: eight shares of the experts, the shared expert counted
# once, sum to the uncut reference's layer
# ------------------------------------------------------------------ #
def test_eight_shares_sum_to_the_uncut_layer():
    full_hf = dict(HF, n_routed_experts=8, router_experts=8, expert_start=0)
    params = _params(full_hf, seed=4)
    lp = _ref_params(params)["layers"][2]       # a sliding MoE layer
    x = jnp.asarray(np.random.default_rng(5).standard_normal((19, 64)),
                    jnp.float32)
    eps = 1e-5
    small = {k: lp[k] for k in ("ln2", "router", "bias")}
    kw = dict(eps=eps, top_k=2, norm_topk=True, scale=1.0)
    whole = moonlight_ref._moe_block(
        x, jnp.zeros_like(x), small,
        {k: lp[k] for k in moonlight_ref._EXPERT_KEYS}, 0, **kw)
    shared = moonlight_ref._moe_shared(
        x, jnp.zeros_like(x),
        {k: lp[k] for k in ("ln2", "s_gate", "s_up", "s_down")}, eps=eps) - x
    # the program: a chip holds ONE of the eight experts; its dropless_moe
    # adds the shared expert, which the sum must count once
    from deepspeed_tpu.inference.v2.modules.attention import _rms_norm
    from deepspeed_tpu.inference.v2.modules.moe import dropless_moe
    mlp = params["layers_2"]["mlp"]
    xm = _rms_norm(x, params["layers_2"]["post_attention_layernorm"]
                   ["scale"], eps)
    total = jnp.zeros_like(x)
    for e in range(8):
        held = {**mlp, "experts": {k: v[e:e + 1]
                                   for k, v in mlp["experts"].items()}}
        total = total + dropless_moe(xm, held, 2, jnp.float32,
                                     renormalize=True, expert_start=e,
                                     routed_scale=1.0) - shared
    assert np.allclose(np.asarray(total + shared),
                       np.asarray(whole + shared), rtol=1e-4, atol=1e-5)
    assert float(jnp.max(jnp.abs(whole))) > 0.01


# ------------------------------------------------------------------ #
# (d) two pools, two row widths
# ------------------------------------------------------------------ #
def test_the_pools_keep_a_row_a_group():
    eng = _engine(_params())
    sm, kv = eng.state_manager, eng.state_manager.kv_cache
    assert eng.model.kv_row == {"ckv": 128, "idx_k": 128}
    assert eng.model.kv_groups == {"window": {
        "layers": [1, 2, 3], "window": WINDOW, "row": {"ckv": 128}}}
    # a sliding row of 64 + 8 pads to one tile here; at the published widths
    assert rd.Dots3NoteConfig().swa.row_width == 1152
    assert rd.Dots3NoteConfig().row_width == 640
    win_rows = (sm.window_pool_blocks + 1) * BLOCK
    shapes = {k: {n: a.shape for n, a in v.items()}
              for k, v in kv.cache.items()}
    assert shapes == {
        "layer_0": {"ckv": (120 * BLOCK, 128), "idx_k": (120 * BLOCK, 128)},
        "layer_1": {"ckv": (win_rows, 128)},
        "layer_2": {"ckv": (win_rows, 128)},
        "layer_3": {"ckv": (win_rows, 128)},
        "layer_4": {"ckv": (120 * BLOCK, 128), "idx_k": (120 * BLOCK, 128)}}
    # bytes by group: two full layers x (128 + 128) x 4 B, three sliding
    # layers x 128 x 4 B (float32 pools here)
    assert kv.per_token_bytes == 2 * 256 * 4
    assert kv.window_layer_token_bytes == 128 * 4
    assert kv.window_token_bytes == 3 * 128 * 4
    assert kv.window_pool_bytes == win_rows * 3 * 128 * 4


def test_two_row_widths_at_the_published_geometry():
    """The cache alone at the cell's widths and counts: 1,152 lanes in the
    window pool, 640 + 128 in the global one, bf16."""
    from deepspeed_tpu.inference.v2.config_v2 import (DSStateManagerConfig,
                                                      KVCacheConfig)
    from deepspeed_tpu.inference.v2.ragged import DSStateManager

    sm = DSStateManager(
        DSStateManagerConfig(max_ragged_batch_size=1024,
                             max_ragged_sequence_count=48,
                             max_context=50176),
        KVCacheConfig(block_size=128, num_blocks=8),
        num_layers=5, num_kv_heads=1, head_dim=640, dtype=jnp.bfloat16,
        kv_row={"ckv": 640, "idx_k": 128},
        kv_groups={"window": {"layers": [1, 2, 3], "window": 513,
                              "row": {"ckv": 1152}}})
    # W - 1 = 4 x 128 + 0: six blocks a sequence and one forward's tokens
    assert sm.window_pool_blocks == 48 * 6 + 1024 // 128 == 296
    assert sm.window_table_bound == -(-(513 + 1024) // 128) + 1 == 14
    kv = sm.kv_cache
    assert kv.cache["layer_2"]["ckv"].shape == (297 * 128, 1152)
    assert kv.cache["layer_4"]["idx_k"].shape == (8 * 128, 128)
    assert kv.per_token_bytes == 2 * 1536 == 3072
    assert kv.window_token_bytes == 3 * 2304 == 6912
    assert kv.window_pool_bytes == 297 * 128 * 6912
    occ = kv_occupancy(sm)
    assert occ["observability/kv_window_pool_bytes"] == 297 * 128 * 6912
    assert occ["observability/kv_pool_bytes"] == 8 * 128 * 3072
    assert occ["observability/kv_window_live_bytes"] == 0


def test_admission_counts_each_pool_and_the_gauges_follow():
    params = _params()
    eng = _engine(params, blocks=40, max_seqs=4)
    sm = eng.state_manager
    assert sm.window_pool_blocks == 4 * (2 + 2) + (4 * 4 + BUDGET) // BLOCK
    ids = _ids(200)
    eng.put([1], [ids[:150].tolist()])
    seq = sm.get_sequence(1)
    # the global table keeps every block, the window table the band's
    assert len(seq.blocks) == 10
    sm.release_windows()
    assert seq.win_first == (150 - WINDOW + 1) // BLOCK == 7
    assert len(seq.win_blocks) == 10 - 7
    occ = kv_occupancy(sm)
    kv = sm.kv_cache
    assert occ["observability/kv_live_bytes"] == 10 * BLOCK * 2 * 256 * 4
    assert occ["observability/kv_window_blocks_live"] == 3
    assert occ["observability/kv_window_live_bytes"] \
        == 3 * BLOCK * kv.window_token_bytes
    # a new sequence's first forward: at most the table bound of blocks
    assert sm.window_blocks_needed(None, 400) == sm.window_table_bound
    assert sm.window_blocks_needed(seq, 1) == 0
    assert eng.can_allocate([2], [300])
    assert not eng.can_allocate([2], [31 * BLOCK])    # the global pool
    eng.flush([1])


@pytest.mark.parametrize("feature, path", [
    ("prefix_cache", "kv_cache.enable_prefix_cache"),
    ("int8_kv", "kv_cache.dtype=int8")])
def test_a_config_the_two_layouts_cannot_serve_is_refused(feature, path):
    kv = {"enable_prefix_cache": True} if feature == "prefix_cache" \
        else {"dtype": "int8"}
    with pytest.raises(CacheLayoutError, match="RaggedDots3Note") as e:
        _engine(_params(), **kv)
    assert path in str(e.value)


def test_every_refusal_names_both_layouts_where_both_refuse():
    """``verify`` is refused by the latent row AND by the two groups: the
    one message says both, from the one place."""
    eng = _engine(_params())
    sm = eng.state_manager
    assert set(sm.unserved) == {"prefix_cache", "host_tier", "kv_handoff",
                                "verify", "decode_loop", "int8_kv"}
    assert "kv_row" in sm.unserved["verify"] \
        and "kv_groups" in sm.unserved["verify"]
    assert "kv_groups" in sm.unserved["decode_loop"] \
        and "kv_row" not in sm.unserved["decode_loop"]
    for feature in sm.unserved:
        with pytest.raises(CacheLayoutError, match="here"):
            sm.require(feature, "here")


# ------------------------------------------------------------------ #
# (e) the banded walk and the band's composition against plain NumPy
# ------------------------------------------------------------------ #
def _band_case(dtype, seed=0, rows=6, heads=3, width=256, rank=128,
               entries=12):
    rng = np.random.default_rng(seed)
    pool = jnp.asarray(rng.standard_normal(((rows * entries + 1) * BLOCK,
                                            width)), dtype)
    tables = rng.permutation(np.arange(1, rows * entries + 1)).reshape(
        rows, entries).astype(np.int32)
    pos = np.asarray([0, 5, WINDOW - 1, WINDOW, 100, 191][:rows], np.int32)
    q = jnp.asarray(0.3 * rng.standard_normal((rows, heads, width)), dtype)
    return q, pool, tables, pos


def _band_numpy(q, pool, tables, pos, window, rank, scale):
    q, pool = np.asarray(q, np.float64), np.asarray(pool, np.float64)
    out = np.zeros(q.shape[:2] + (rank,))
    for r, p in enumerate(pos):
        if p < 0:
            continue
        keys = np.arange(max(0, p - window + 1), p + 1)
        ctx = pool[tables[r][keys // BLOCK] * BLOCK + keys % BLOCK]
        s = q[r] @ ctx.T * scale
        w = np.exp(s - s.max(-1, keepdims=True))
        out[r] = (w / w.sum(-1, keepdims=True)) @ ctx[:, :rank]
    return out


@pytest.mark.parametrize("window", [WINDOW, 33, 16, 1])
def test_the_banded_walk_is_the_bands_softmax(window):
    """Rows inside the window, on its edge and far past it; every table
    entry below a row's band is set to the trash block, which holds NaN:
    the walk must not touch it."""
    q, pool, tables, pos = _band_case(jnp.float32)
    dead = np.arange(tables.shape[1])[None, :] \
        < (np.maximum(pos - window + 1, 0) // BLOCK)[:, None]
    tables = np.where(dead, 0, tables)
    pool = pool.at[:BLOCK].set(jnp.nan)
    want = _band_numpy(q, pool, tables, pos, window, 128, 0.2)
    slot = jnp.arange(len(pos), dtype=jnp.int32)
    got = lf.latent_decode_attention(
        q, pool, jnp.asarray(tables), slot, jnp.asarray(pos),
        block_size=BLOCK, value_dim=128, scale=0.2, window=window,
        interpret=True)
    assert np.isfinite(np.asarray(got)).all()
    assert np.allclose(np.asarray(got), want, rtol=2e-5, atol=2e-5)
    xla = rd.banded_absorbed_read_xla(
        q[:, None], pool, jnp.asarray(tables), jnp.asarray(pos)[:, None],
        BLOCK, 128, 0.2, window)[:, 0]
    assert np.allclose(np.asarray(xla), want, rtol=2e-5, atol=2e-5)


def test_the_walk_skips_pad_rows_and_takes_slots_in_any_order():
    q, pool, tables, pos = _band_case(jnp.float32, seed=1)
    pos = np.asarray([191, -1, 40, -1, 3, 120], np.int32)
    slot = np.asarray([4, 0, 2, 0, 5, 1], np.int32)
    got = np.asarray(lf.latent_decode_attention(
        q, pool, jnp.asarray(tables), jnp.asarray(slot), jnp.asarray(pos),
        block_size=BLOCK, value_dim=128, scale=0.2, window=WINDOW,
        interpret=True))
    want = _band_numpy(q, pool, tables[slot], pos, WINDOW, 128, 0.2)
    assert (got[pos < 0] == 0).all()
    assert np.allclose(got, want, rtol=2e-5, atol=2e-5)


def test_a_tiles_band_is_its_rows_bands():
    """The composition over a tile's shared band of blocks: 16 rows at
    consecutive positions, pad rows behind a short chunk's end."""
    rng = np.random.default_rng(2)
    _q, pool, tables, _ = _band_case(jnp.float32, seed=2, rows=3)
    starts, lens = [0, 96, 150], [16, 16, 9]
    pos = np.stack([np.where(np.arange(TILE) < n, s + np.arange(TILE), -1)
                    for s, n in zip(starts, lens)]).astype(np.int32)
    q = jnp.asarray(0.3 * rng.standard_normal((3, TILE, 3, 256)),
                    jnp.float32)
    got = np.asarray(rd.banded_absorbed_read_xla(
        q, pool, jnp.asarray(tables), jnp.asarray(pos), BLOCK, 128, 0.2,
        WINDOW))
    assert np.isfinite(got).all()
    for t in range(3):
        want = _band_numpy(q[t], pool, np.repeat(tables[t:t + 1], TILE, 0),
                           pos[t], WINDOW, 128, 0.2)
        live = pos[t] >= 0
        assert np.allclose(got[t][live], want[live], rtol=2e-5, atol=2e-5)


def test_the_walks_rule_is_the_rows_width():
    assert lf.latent_walk_usable(1024, 128)         # nope 192 walks
    assert not lf.latent_walk_usable(1000, 128)
    assert not lf.latent_kernels_usable(1024, 192, 128, 128)
    assert lf.latent_kernels_usable(512, 128, 128, 128)
    assert lf.latent_row_width(1024, 64) == 1152


def test_the_cells_walk_lowers_for_the_tpu(monkeypatch):
    """The banded walk at the published sliding geometry (64 heads against
    a 1,152-lane row, window 513, the cell's tables) lowers through Mosaic
    from here."""
    monkeypatch.setattr(lf, "on_tpu", lambda: True)
    s_rows, entries, bs = 48, 392, 128
    shapes = (jax.ShapeDtypeStruct((s_rows, 64, 1152), jnp.bfloat16),
              jax.ShapeDtypeStruct((297 * bs, 1152), jnp.bfloat16),
              jax.ShapeDtypeStruct((s_rows, entries), jnp.int32),
              jax.ShapeDtypeStruct((s_rows,), jnp.int32),
              jax.ShapeDtypeStruct((s_rows,), jnp.int32))
    f = lambda q, pool, tables, slot, pos: lf.latent_decode_attention(
        q, pool, tables, slot, pos, block_size=bs, value_dim=1024,
        scale=256 ** -0.5, window=513)
    text = jax.jit(f).trace(*shapes).lower(
        lowering_platforms=("tpu",)).as_text()
    assert "tpu_custom_call" in text and "_latent_decode_kernel" in text


# ------------------------------------------------------------------ #
# (f) counters and spans
# ------------------------------------------------------------------ #
def test_a_dispatch_counts_both_kinds_of_read(clean):
    params, ids, _ = clean
    eng = _engine(params)
    tracer = Tracer()
    sched = ContinuousBatchScheduler(eng, tracer=tracer)
    sched.submit(ids[:100].tolist(),
                 SamplingParams(greedy=True, max_new_tokens=3))
    sched.run_until_idle()
    builds = [r["attrs"] for r in tracer.records()
              if r["name"] == "engine/build_batch" and r.get("attrs")]
    first, second = builds[0], builds[1]
    # the first chunk: 64 rows from 0; a band of 37, a top-k of 24
    assert first["attn_pairs_win"] == sum(min(t + 1, WINDOW)
                                          for t in range(64))
    assert first["ctx_rows_win"] == 64
    assert first["sel_pairs"] == sum(min(t + 1, TOPK) for t in range(64))
    # the second: 36 rows from 64, the band's 36 rows before it
    assert second["attn_pairs_win"] == 36 * WINDOW
    assert second["ctx_rows_win"] == 36 + WINDOW - 1
    assert second["win_pool_blocks"] == eng.state_manager.window_pool_blocks
    prep = [r["attrs"] for r in tracer.records()
            if r["name"] == "engine/decode_prep" and r.get("attrs")]
    assert prep and all(p["read_keys_win"] == 3 * WINDOW for p in prep)
    assert all(p["read_blocks_win"] % 3 == 0 for p in prep)


def test_the_sliding_layers_scopes_are_in_the_programs_text(clean):
    params, _, _ = clean
    eng = _engine(params)
    step = eng._get_step(MAX_SEQS + 64, TILE)
    text = step.lower(eng.params, eng.state_manager.kv_cache.cache,
                      jnp.zeros((5 * (MAX_SEQS + 64) + 2 * MAX_SEQS * 32
                                 + 2 * MAX_SEQS,), jnp.int32)).as_text(
                                     debug_info=True)
    for scope in ("layers_1/attn/window_read", "layers_2/attn/window_prefill",
                  "layers_3/attn/gate", "layers_0/attn/sparse_read",
                  "layers_4/attn/gate", "layers_0/attn/index_topk"):
        assert scope in text, scope
    assert "layers_0/attn/window" not in text
    assert "layers_1/attn/sparse_read" not in text
