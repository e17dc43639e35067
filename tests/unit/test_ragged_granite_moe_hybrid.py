"""Granite-4.0-H (``model_type: granitemoehybrid``) through the normal
serving path at a small size on the CPU: ``RaggedGraniteMoeHybrid`` ->
``InferenceEngineV2`` (``put``, ``decode_step``, two-segment batches, the
state slot pool with a float32 Mamba-2 state beside a convolution tail, a
GQA pool read without positions at the muP scale, a share of the routed
experts beside a shared expert) -> ``ContinuousBatchScheduler``, against the
benchmark's plain float32 reference
(``benchmark/reference/granite_moe_hybrid.py``: a ``lax.scan`` over the
tokens, a padded convolution, no cache, no state).

Everything that makes the model what it is is drawn away from its neutral
value so that leaving it out fails: norm weights (the gated one too) uniform
in 0.5 .. 1.5, ``D`` and the convolution's bias N(0, 1), ``A`` a ramp 1/8 ..
2 over the heads with ``dt`` about 0.02 (a state that remembers tens to
hundreds of tokens: chunks here are 16 to 64 tokens long), ``q`` and ``k``
large enough that scores ``q . k / 128`` are of unit size.
"""

import json
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

from benchmark.families import granite_moe_hybrid as family   # noqa: E402
from benchmark.reference import granite_moe_hybrid as reference  # noqa: E402
from benchmark.tools.calls.pr59_faults import FAULTS, fault   # noqa: E402
from deepspeed_tpu.inference.v2 import (                      # noqa: E402
    InferenceEngineV2, RaggedInferenceEngineConfig)
from deepspeed_tpu.inference.v2.model_implementations import (  # noqa: E402
    ragged_granite_moe_hybrid as rg)
from deepspeed_tpu.inference.v2.ragged.kv_cache import (      # noqa: E402
    CacheLayoutError)
from deepspeed_tpu.serving import (ContinuousBatchScheduler,  # noqa: E402
                                   SamplingParams)

# the published keys at the test's size: attention at layer 2 of 4, four of
# the router's eight experts held
HF = {"model_type": "granitemoehybrid", "vocab_size": 256, "hidden_size": 64,
      "intermediate_size": 32, "shared_intermediate_size": 48,
      "num_hidden_layers": 4,
      "layer_types": ["mamba", "mamba", "attention", "mamba"],
      "num_attention_heads": 4, "num_key_value_heads": 2,
      "mamba_n_heads": 8, "mamba_d_head": 16, "mamba_d_state": 8,
      "mamba_n_groups": 1, "mamba_d_conv": 4, "mamba_expand": 2,
      "mamba_conv_bias": True, "mamba_proj_bias": False,
      "attention_bias": False, "position_embedding_type": "nope",
      "num_local_experts": 4, "router_experts": 8, "expert_start": 2,
      "num_experts_per_tok": 2, "embedding_multiplier": 12,
      "residual_multiplier": 0.22, "attention_multiplier": 0.0078125,
      "logits_scaling": 16, "rms_norm_eps": 1e-5,
      "max_position_embeddings": 512, "tie_word_embeddings": True}
MAX_SEQS, BUDGET, TILE, BLOCK = 8, 64, 16, 8

# float32 engine against the float32 reference, largest |difference| over
# the largest |reference logit|: the same float32 mathematics in another
# order (chunks in the matmul form through ragged rows and the slot pool
# against one scan over the sequence), 1e-7 to 2e-7 here; every fault below
# reads 100 times the limit or more.
F32_TOL = 1e-4


def _config(dtype=jnp.float32, hf=HF):
    cfg = family.program_config(hf)
    cfg.dtype = dtype
    return cfg


def _params(seed=0, hf=HF):
    rng = np.random.default_rng(seed)
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        rg.param_shapes(_config(hf=hf)))
    out = []
    for path, leaf in flat:
        names = [str(getattr(p, "key", p)) for p in path]
        shape, a = leaf.shape, rng.standard_normal(leaf.shape)
        if names[-1] == "scale":
            a = rng.uniform(0.5, 1.5, shape)
        elif names[-1] == "A_log":          # [H]: A from 1/8 to 2
            a = np.linspace(np.log(0.125), np.log(2.0), shape[0])
        elif names[-1] == "dt_bias":
            a = -4.0 + 0.5 * a              # dt about 0.02
        elif names[-1] == "embedding":
            a = 0.1 * a
        elif names[-1] in ("w_gate", "w_up", "w_down"):
            a = a * shape[1] ** -0.5
        elif names[-2] in ("q_proj", "k_proj"):
            a = 7.0 * a * shape[0] ** -0.5
        elif names[-1] not in ("D", "bias"):
            a = a * shape[0] ** -0.5
        out.append(jnp.asarray(a, jnp.float32))
    return jax.tree_util.tree_unflatten(treedef, out)


def _reference_params(params):
    """The family's mapping without its seeded decay (these weights carry
    their own ``A_log``, ``D`` and ``dt_bias``)."""
    old = family._seeded_ssd
    family._seeded_ssd = lambda tree: tree
    try:
        return family.reference_params(params)
    finally:
        family._seeded_ssd = old


def _engine(params, blocks=80, max_context=256, max_seqs=MAX_SEQS,
            interpret=None, hf=HF):
    model = rg.RaggedGraniteMoeHybrid(_config(hf=hf), BLOCK)
    model.interpret = interpret
    eng = InferenceEngineV2(
        model, params, RaggedInferenceEngineConfig.from_dict({
            "state_manager": {"max_ragged_batch_size": BUDGET,
                              "max_ragged_sequence_count": max_seqs,
                              "max_context": max_context},
            "kv_cache": {"block_size": BLOCK, "num_blocks": blocks}}))
    eng.PREFILL_TILE = TILE          # a 64-token budget of whole tiles
    return eng


def _ids(n, seed=3):
    return np.random.default_rng(seed).integers(0, HF["vocab_size"],
                                                size=(n,))


def _serve(eng, ids, n_prompt, uid=7, chunks=None):
    """``put`` the prompt (in the given chunk sizes, or as the engine
    splits it), then decode the rest teacher-forced."""
    at = 0
    for n in chunks or [n_prompt]:
        row = eng.put([uid], [ids[at:at + n].tolist()])[uid]
        at += n
    assert at == n_prompt
    got = [np.asarray(row, np.float32)]
    for t in ids[n_prompt:]:
        row = eng.decode_step([uid], [int(t)])
        got.append(np.asarray(jax.device_get(row), np.float32)[0])
    eng.flush([uid])
    return np.stack(got)


def _want(params, ids, n_prompt, hf=HF):
    return reference.logits_at(_reference_params(params), ids, hf,
                               rows=list(range(n_prompt - 1, len(ids))))


def _gap(got, want) -> float:
    assert got.shape == want.shape and np.isfinite(got).all()
    return float(np.max(np.abs(got - want)) / np.max(np.abs(want)))


def _states(eng):
    """Every live slot's leaves on the host (the scratch slot left out)."""
    pool = eng.state_manager.state_pool
    return {(k, leaf): np.asarray(a)[:pool.num_slots]
            for k, v in eng.state_manager.kv_cache.cache.items()
            if "ssm" in v for leaf, a in v.items()}


# ------------------------------------------------------------------ #
# (a) one prompt in 1, 2 and 4 chunks, among them a chunk of one row, then
# 5 decode steps; the kernels in interpret mode and the compositions
# ------------------------------------------------------------------ #
@pytest.mark.parametrize("n_prompt, chunks, interpret", [
    (40, None, None), (100, None, None), (100, None, True),
    (66, [64, 1, 1], None), (70, [3, 1, 2, 64], True)],
    ids=["1_chunk", "2_chunks", "2_chunks_kernels", "chunks_of_one_row",
         "chunks_shorter_than_the_taps_kernels"])
def test_f32_engine_matches_reference(n_prompt, chunks, interpret):
    params, ids = _params(), _ids(n_prompt + 5)
    eng = _engine(params, interpret=interpret)
    assert _gap(_serve(eng, ids, n_prompt, chunks=chunks),
                _want(params, ids, n_prompt)) <= F32_TOL
    assert eng.state_manager.state_pool.held == 0


@pytest.mark.parametrize("name", FAULTS)
def test_a_seeded_fault_fails_the_tolerance(name):
    """The negative cases the chip check is held to
    (``benchmark/tools/calls/pr59_faults.py``): each alone moves the logits
    by 100 times the float32 limit or more.  The sequence's slot held
    another sequence's state before."""
    params, ids = _params(), _ids(100 + 5)
    want = _want(params, ids, 100)
    with fault(name):
        eng = _engine(params, max_seqs=1)
        eng.put([3], [_ids(40, seed=8).tolist()])
        eng.flush([3])
        got = _serve(eng, ids, 100)
    assert _gap(got, want) > 100 * F32_TOL


def test_a_state_stored_in_bf16_is_told_from_float32_here():
    """At float32 the check sees a state rounded to bf16 wherever it is
    stored; whether the bf16 engine's check on the chip does is PERF.md's
    to say (PR 59)."""
    params, ids = _params(), _ids(100 + 5)
    with fault("bf16_state"):
        got = _serve(_engine(params), ids, 100)
    assert _gap(got, _want(params, ids, 100)) > 2 * F32_TOL


# ------------------------------------------------------------------ #
# (b) six sequences interleaved through the scheduler: joins, a flush, a
# reused slot, a preemption by recompute
# ------------------------------------------------------------------ #
def _greedy(n):
    return SamplingParams(greedy=True, max_new_tokens=n)


PROMPT_LENS, NEW = (150, 40, 90, 7, 33, 65), (4, 9, 5, 12, 6, 5)


@pytest.fixture(scope="module")
def served():
    params = _params()
    prompts = [_ids(n, seed=10 + i).tolist()
               for i, n in enumerate(PROMPT_LENS)]
    return params, prompts


def test_interleaved_logits_match_each_reference(served):
    from interleaved_logits import serve_and_compare

    params, prompts = served
    eng = _engine(params)
    out = serve_and_compare(eng, reference, _reference_params(params), HF,
                            prompts, NEW)
    assert len(out["gaps"]) == 6 and max(out["gaps"]) <= F32_TOL, out
    assert eng.state_manager.state_pool.held == 0


def test_a_reused_slot_starts_from_zero(served):
    """Three slots, six requests: the later ones take slots the earlier
    ones left, whose state is whatever they held (a slot is never cleared
    on release; a chunk that starts at position 0 reads zeros)."""
    from interleaved_logits import serve_and_compare

    params, prompts = served
    eng = _engine(params, max_seqs=3)
    out = serve_and_compare(eng, reference, _reference_params(params), HF,
                            prompts, NEW)
    assert max(out["gaps"]) <= F32_TOL, out
    assert all(np.abs(a).max() > 0 for a in _states(eng).values())
    assert eng.state_manager.state_pool.free == 3


def test_preemption_by_recompute_gives_the_same_logits(served):
    params, prompts = served

    alone = ContinuousBatchScheduler(_engine(params))

    def solo(p, n):
        req = alone.submit(list(p), _greedy(n))
        alone.run_until_idle()
        return list(req.generated)

    news = (30, 25, 40, 30)
    # 23 usable blocks of 8 tokens: the four requests together outgrow
    # them while decoding, so the newest is preempted and recomputed from
    # a zeroed slot
    eng = _engine(params, blocks=24)
    sched = ContinuousBatchScheduler(eng)
    reqs = [sched.submit(p, _greedy(n)) for p, n in zip(prompts[1:5], news)]
    sched.run_until_idle()
    assert sched.metrics.preemptions >= 1
    assert [list(r.generated) for r in reqs] == [
        solo(p, n) for p, n in zip(prompts[1:5], news)]
    assert eng.state_manager.state_pool.held == 0
    # and the logits of a recomputed sequence are the reference's
    ids = np.asarray(prompts[4] + list(reqs[3].generated))
    got = _serve(eng, ids, len(prompts[4]))
    assert _gap(got, _want(params, ids, len(prompts[4]))) <= F32_TOL


@pytest.mark.parametrize("interpret", [None, True],
                         ids=["compositions", "kernels"])
def test_pad_rows_and_padded_tails_change_no_other_slot(interpret):
    eng = _engine(_params(), max_seqs=4, interpret=interpret)
    eng.put([1], [_ids(30, seed=1).tolist()])
    eng.put([2], [_ids(50, seed=2).tolist()])
    s1, s2 = (eng.state_manager.get_sequence(u).state_slot for u in (1, 2))
    before = _states(eng)
    eng.decode_step([1], [5])       # three pad rows beside it
    eng.put([3], [_ids(21, seed=3).tolist()])   # a tile with 11 pad rows
    s3 = eng.state_manager.get_sequence(3).state_slot
    after = _states(eng)
    for key, a in before.items():
        b = after[key]
        assert np.array_equal(a[s2], b[s2]), key            # bitwise
        assert not np.array_equal(a[s1], b[s1])
        untouched = [s for s in range(4) if s not in (s1, s3)]
        assert np.array_equal(a[untouched], b[untouched])


# ------------------------------------------------------------------ #
# (c) the share of the experts: four chips' routed parts and the shared
# expert once are the uncut layer
# ------------------------------------------------------------------ #
def test_four_shares_and_the_shared_expert_add_up_to_the_uncut_layer():
    from deepspeed_tpu.inference.v2.modules.moe import dropless_moe

    uncut = {**HF, "num_local_experts": 8, "expert_start": 0}
    moe = _params(seed=5, hf=uncut)["layers_0"]["block_sparse_moe"]
    ref = _reference_params(_params(seed=5, hf=uncut))["layers"][0]
    u = jnp.asarray(np.random.default_rng(6).standard_normal((37, 64)),
                    jnp.float32)
    with jax.default_matmul_precision("highest"):
        whole = reference.ffn(u, ref, top_k=2, expert_start=0)
        shared = (reference._silu(u @ ref["s_gate"]) * (u @ ref["s_up"])) \
            @ ref["s_down"]
        parts = []
        for chip in range(4):
            held = slice(2 * chip, 2 * chip + 2)
            share = {**moe, "experts": {k: v[held]
                                        for k, v in moe["experts"].items()}}
            parts.append(dropless_moe(u, share, 2, jnp.float32,
                                      renormalize=True,
                                      expert_start=2 * chip) - shared)
    assert float(jnp.max(jnp.abs(sum(parts[1:], parts[0])))) > 0.1
    np.testing.assert_allclose(sum(parts[1:], parts[0]) + shared, whole,
                               rtol=1e-4, atol=1e-4)
    # the router's top-k of the logits then a softmax over them is the
    # program's softmax, top-k, renormalised
    idx, w = reference.route(u, ref["router"], 2)
    assert np.allclose(np.asarray(w).sum(-1), 1.0, atol=1e-6)


# ------------------------------------------------------------------ #
# (d) what the state costs, in bytes, and the parameter count
# ------------------------------------------------------------------ #
def test_bytes_a_token_and_a_sequence_hold():
    eng = _engine(_params(), max_seqs=3)
    pool = eng.state_manager.state_pool
    # 3 Mamba-2 layers x (8 x 128 float32 + a tail of 3 x 144 float32 at
    # this dtype, which holds 512 lanes)
    assert pool.per_sequence_bytes == 3 * (8 * 128 * 4 + 512 * 4)
    assert pool.total_bytes == 4 * pool.per_sequence_bytes
    eng.put([1], [_ids(20).tolist()])
    assert pool.held_bytes == pool.per_sequence_bytes
    cache = eng.state_manager.kv_cache.cache
    assert cache["layer_0"]["ssm"].shape == (4, 8, 128)
    assert cache["layer_0"]["ssm"].dtype == jnp.float32
    assert cache["layer_0"]["conv"].shape == (4, 3 * 144)
    assert set(cache["layer_2"]) == {"k", "v"}


def test_bytes_and_parameters_at_the_published_widths():
    hf = json.load(open(os.path.join(
        _REPO, "benchmark/configs/granite-4.0-h-small-serve-1chip.json")))
    model = rg.RaggedGraniteMoeHybrid(family.program_config(hf), 128)
    spec = model.state_spec
    assert spec["layers"] == [i for i in range(10) if i != 5]
    assert spec["leaves"] == {"ssm": ((128, 8192), jnp.float32),
                              "conv": ((3 * 8448,), jnp.bfloat16)}
    from deepspeed_tpu.inference.v2.ragged.kv_cache import flat_row
    from deepspeed_tpu.inference.v2.ragged.state_pool import StateSlotPool

    pool = StateSlotPool(128, spec["layers"], spec["leaves"])
    assert pool.per_sequence_bytes == 9 * 4_244_992 == 38_204_928
    assert pool.total_bytes == 129 * 38_204_928
    # the attention layer's row: 8 heads of 128, stored flat; 4 KB a token
    assert flat_row(jnp.bfloat16, model.num_kv_heads, model.head_dim)
    shapes = family.shapes(hf)
    assert shapes["state_bytes_per_seq"] == 38_204_928
    assert shapes["kv_bytes_per_token"] == 4096
    count = lambda tree: sum(int(np.prod(l.shape))
                             for l in jax.tree_util.tree_leaves(tree))
    tree = family.serve_param_shapes(hf)
    assert count(tree["layers_0"]["mamba"]) == 102_286_976
    assert count(tree["layers_5"]["self_attn"]) == 41_943_040
    assert count(tree["layers_0"]) == 291_333_760
    assert count(tree["layers_5"]) == 230_989_824
    assert count(tree) == shapes["total_params"] == 2_955_758_208
    # the whole model by the same count: the published 32B
    whole = rg.param_shapes(rg.GraniteMoeHybridConfig())
    assert count(whole) == 36 * 800_941_696 + 4 * 740_597_760 \
        + 411_041_792 + 4096
    assert model.config.query_scale == pytest.approx(128 ** 0.5 / 128)


def test_what_the_model_does_not_compute_is_refused_by_name():
    for key, value, match in (
            ("mamba_n_groups", 8, "mamba_n_groups=8"),
            ("position_embedding_type", "rope", "'rope'"),
            ("mamba_proj_bias", True, "mamba_proj_bias=True"),
            ("attention_bias", True, "attention_bias=True"),
            ("tie_word_embeddings", False, "tie_word_embeddings=False"),
            ("layer_types", ["mamba", "mamba", "mlp", "mamba"], "'mlp'")):
        with pytest.raises(NotImplementedError, match=match):
            family.program_config({**HF, key: value})
    with pytest.raises(ValueError, match="mamba_n_heads x mamba_d_head"):
        family.program_config({**HF, "mamba_d_head": 32})
    with pytest.raises(ValueError, match="num_hidden_layers is 4"):
        family.program_config({**HF, "layer_types": ["mamba"] * 3})
    with pytest.raises(ValueError, match="one group"):
        reference.logits_at({}, _ids(4), {**HF, "mamba_n_groups": 8}, [3])
    mesh = jax.sharding.Mesh(np.asarray(jax.devices()[:2]), ("model",))
    with pytest.raises(NotImplementedError, match="tp = 1"):
        rg.RaggedGraniteMoeHybrid(_config(), BLOCK, mesh=mesh)
    # a batch packed back to back has no tile to carry a state along
    model = rg.RaggedGraniteMoeHybrid(_config(), BLOCK)
    with pytest.raises(CacheLayoutError, match="two-segment"):
        model({}, {}, {})
    from deepspeed_tpu.inference.v2.model_implementations import HF_MODELS

    assert HF_MODELS["granitemoehybrid"] == (rg.RaggedGraniteMoeHybrid, True)


def test_cache_features_are_refused_by_the_table():
    eng = _engine(_params(), max_seqs=2)
    for feature in ("prefix_cache", "host_tier", "kv_handoff", "verify",
                    "decode_loop"):
        with pytest.raises(CacheLayoutError, match="state_spec"):
            eng.state_manager.require(feature, "test")


# ------------------------------------------------------------------ #
# (e) the device scopes exist
# ------------------------------------------------------------------ #
def test_device_scopes_of_a_mixed_batch():
    eng = _engine(_params())
    eng.put([1], [_ids(20).tolist()])
    eng.put([1, 2], [[5], _ids(30, seed=2).tolist()])
    text = "\n".join(eng.lower_step(k).as_text(debug_info=True)
                     for k in eng.step_keys)
    for scope in ("layers_0/mamba2/in_proj", "layers_0/mamba2/conv",
                  "layers_0/mamba2/scan", "layers_0/mamba2/out",
                  "layers_2/attn/qkv", "layers_2/attn/rope_insert",
                  "layers_2/attn/out_proj", "layers_0/moe/router",
                  "layers_0/moe/dispatch", "layers_0/moe/experts",
                  "layers_0/moe/combine", "layers_2/moe/shared", "lm_head"):
        assert scope in text, scope
    assert "layers_2/mamba2" not in text and "layers_0/attn" not in text
