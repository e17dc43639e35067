"""OLMoE (``model_type: olmoe``) through the normal serving path at a small
size on the CPU: ``RaggedMixtral`` -> ``InferenceEngineV2`` (``put``,
``decode_step``, two-segment batches) against the benchmark's plain float32
reference (``benchmark/reference/olmoe.py``; there is one copy, the
benchmark's), the grouped GEMM at 64 experts, and a checkpoint under the
published tensor names.

What makes OLMoE not Mixtral is drawn away from its neutral value so that
leaving it out fails: the q/k RMSNorm scales are uniform in 0.5 .. 1.5 (1
would still normalise, so the scale-free part is checked by the negative
case), the router is N(0, 4/H) (logits of spread ~2: top-2 weights that sum
to 0.4 .. 0.9, so renormalising them moves the output by tens of percent).
"""

import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

_REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)

from benchmark.families import olmoe as family          # noqa: E402
from benchmark.reference import olmoe as reference      # noqa: E402
from deepspeed_tpu.inference.v2 import (                 # noqa: E402
    InferenceEngineV2, RaggedInferenceEngineConfig)
from deepspeed_tpu.inference.v2.model_implementations import (  # noqa: E402
    RaggedMixtral)
from deepspeed_tpu.inference.v2.modules.moe import dropless_moe  # noqa: E402
from deepspeed_tpu.models.mixtral import (               # noqa: E402
    MixtralConfig, MixtralForCausalLM)
from deepspeed_tpu.ops import grouped_gemm              # noqa: E402

# the published keys at the test's size: what the reference and the family
# adapter read
HF = {"model_type": "olmoe", "vocab_size": 256, "hidden_size": 64,
      "intermediate_size": 32, "num_hidden_layers": 2,
      "num_attention_heads": 4, "num_key_value_heads": 4,
      "max_position_embeddings": 256, "rms_norm_eps": 1e-5,
      "rope_theta": 10000, "num_experts": 8, "num_experts_per_tok": 2,
      "norm_topk_prob": False, "tie_word_embeddings": False,
      "clip_qkv": None, "attention_bias": False, "rope_scaling": None}
MAX_SEQS, BUDGET, TILE, BLOCK = 4, 64, 16, 8
N_PROMPT, N_DECODE = 90, 6          # 90 > 64: two forwards, six tiles

# float32 engine against the float32 reference, largest |difference| over
# the largest |reference logit|.  Both compute the same float32 mathematics
# in another order (flat [T, H] rows through the cache against one sequence
# at a time; XLA's CPU dots accumulate in float32): the gap is rounding,
# measured 4e-7 here.  1e-4 is ~250x that and ~100x below what the model's
# own parts move the logits by when left out (the negative cases below,
# measured: top-k weights renormalised 0.012, no q/k normalisation 0.41).
F32_TOL = 1e-4
LEFT_OUT_MIN = 50 * F32_TOL
# bf16 engine (bf16 weights, activations and KV pool) against the float32
# reference on the SAME bf16-rounded weights.  Two sources: bf16 activation
# rounding (2^-9 relative a rounding, ~10 roundings a layer) and ROUTING
# FLIPS: where a token's 2nd and 3rd router probabilities lie within that
# rounding of each other the bf16 forward picks the other expert, which
# moves that token's FFN output by about p_2 x |y_e - y_e'|.  Measured
# here: gap 0.0069, 99.0% of the 192 (token, layer) routings agree.  0.015
# is about twice the measured gap and half the benchmark's own limit for a
# bf16 engine (``LOGIT_TOL`` of ``runners/serve_ragged.py``).  It does not
# catch a renormalised top-k (0.012 in float32), and no bf16 engine can show
# the router's precision (its activations and weights are bf16 values, so
# the router GEMM is exact either way: PERF.md, PR 25): the float32 cases
# catch both (1e-4, and routings that must agree exactly), and
# ``chip_smoke.py``'s router floor the second on the chip.
BF16_TOL = 0.015
BF16_MIN_ROUTING_AGREEMENT = 0.90


def _config(dtype, hf=HF) -> MixtralConfig:
    cfg = family.program_config(hf)
    cfg.dtype = dtype
    return cfg


def _params(hf=HF, seed=0):
    """The training model's own tree (float32), every OLMoE-specific leaf
    drawn away from its neutral value."""
    cfg = _config(jnp.float32, hf)
    params = MixtralForCausalLM(cfg).init(
        jax.random.key(seed), np.zeros((1, 8), np.int32))["params"]
    rng = np.random.default_rng(seed + 1)
    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    out = []
    for path, leaf in flat:
        names = [str(getattr(p, "key", p)) for p in path]
        if names[-1] == "scale":
            leaf = rng.uniform(0.5, 1.5, leaf.shape).astype(np.float32)
        elif "wg" in names:
            leaf = (2.0 * leaf.shape[0] ** -0.5
                    * rng.standard_normal(leaf.shape)).astype(np.float32)
        out.append(jnp.asarray(leaf))
    return jax.tree_util.tree_unflatten(treedef, out)


def _engine(params, dtype, hf=HF, model=None):
    cfg = _config(dtype, hf)
    eng = InferenceEngineV2(
        model or RaggedMixtral(cfg, BLOCK),
        jax.tree.map(lambda a: a.astype(dtype), params),
        RaggedInferenceEngineConfig.from_dict({
            "state_manager": {"max_ragged_batch_size": BUDGET,
                              "max_ragged_sequence_count": MAX_SEQS,
                              "max_context": 128},
            "kv_cache": {"block_size": BLOCK, "num_blocks": 40}}))
    eng.PREFILL_TILE = TILE          # a 64-token budget of whole tiles
    return eng


def _ids(seed=3, n=N_PROMPT + N_DECODE):
    return np.random.default_rng(seed).integers(0, HF["vocab_size"],
                                                size=(n,))


def _serve(eng, ids, n_prompt=N_PROMPT, uid=7):
    """Prefill through ``put`` (chunked by the budget), then the given
    tokens through ``decode_step``: logits at the last prompt position and
    at every decoded one, as the benchmark's check takes them."""
    got = [np.asarray(eng.put([uid], [ids[:n_prompt].tolist()])[uid],
                      np.float32)]
    for t in ids[n_prompt:]:
        row = eng.decode_step([uid], [int(t)])
        got.append(np.asarray(jax.device_get(row), np.float32)[0])
    eng.flush([uid])
    return np.stack(got)


def _want(params, ids, hf=HF, n_prompt=N_PROMPT):
    return reference.logits_at(
        family.reference_params(params), ids, hf,
        rows=list(range(n_prompt - 1, len(ids))))


def _gap(got, want) -> float:
    assert got.shape == want.shape and np.isfinite(got).all()
    return float(np.max(np.abs(got - want)) / np.max(np.abs(want)))


@pytest.fixture
def routed(monkeypatch):
    """Every routing the program makes, in order: one [T, k] array of
    expert indices per layer per forward."""
    calls = []
    real = grouped_gemm.exact_topk_routing

    def recording(logits, k, renormalize=True):
        topi, topw = real(logits, k, renormalize)
        jax.debug.callback(lambda a: calls.append(np.asarray(a)), topi,
                           ordered=True)
        return topi, topw

    monkeypatch.setattr(grouped_gemm, "exact_topk_routing", recording)
    return calls


def _real_rows(calls, layers=HF["num_hidden_layers"]):
    """[layers, tokens, k]: the recorded routings of the real rows of
    ``_serve``'s forwards.  Two-segment ``put`` batches hold the chunk's
    tokens after ``MAX_SEQS`` single-token rows (90 tokens under a 64-token
    budget: chunks of 64 and 26); a decode step holds its sequence in row
    0."""
    jax.effects_barrier()
    per_fwd = [calls[i:i + layers] for i in range(0, len(calls), layers)]
    chunks = [BUDGET, N_PROMPT - BUDGET]
    rows = []
    for n, fwd in enumerate(per_fwd):
        lo, hi = (MAX_SEQS, MAX_SEQS + chunks[n]) if n < len(chunks) \
            else (0, 1)
        rows.append(np.stack([a[lo:hi] for a in fwd]))
    return np.sort(np.concatenate(rows, axis=1), axis=-1)


# ------------------------------------------------------------------ #
# (a), (b): the float32 engine against the reference, the router's flag
# in both positions
# ------------------------------------------------------------------ #
@pytest.mark.parametrize("norm_topk", [False, True],
                         ids=["olmoe_unnormalised", "norm_topk_prob"])
def test_f32_engine_matches_reference(norm_topk, routed):
    hf = dict(HF, norm_topk_prob=norm_topk)
    params, ids = _params(hf), _ids()
    routed.clear()              # the training model's init routes too
    got = _serve(_engine(params, jnp.float32, hf), ids)
    assert _gap(got, _want(params, ids, hf)) <= F32_TOL
    # a float32 router routes exactly as the reference does: a router
    # computed in bf16 flips a few percent of these
    want = np.sort(reference.routings(family.reference_params(params), ids,
                                      hf), axis=-1)
    np.testing.assert_array_equal(_real_rows(routed), want)


@pytest.mark.parametrize("broken", ["no_qk_norm", "router_default_swapped",
                                    "renormalised_top_k"])
def test_f32_engine_fails_when_a_part_is_left_out(broken):
    """The tolerance is tight enough: without the q/k normalisation, or
    with the router's weights renormalised (by the flag or by a swapped
    default of ``exact_topk_routing``), the engine is far off the
    reference."""
    params, ids = _params(), _ids()
    served, hf, model = params, HF, None
    if broken == "no_qk_norm":
        for i in range(HF["num_hidden_layers"]):
            att = dict(served[f"layers_{i}"]["self_attn"])
            att.pop("q_norm"), att.pop("k_norm")
            served = {**served, f"layers_{i}": {
                **served[f"layers_{i}"], "self_attn": att}}
    elif broken == "router_default_swapped":
        # what a MixtralConfig with the field left at its default serves
        cfg = _config(jnp.float32)
        cfg.norm_topk_prob = MixtralConfig.norm_topk_prob
        model = RaggedMixtral(cfg, BLOCK)
    else:
        hf = dict(HF, norm_topk_prob=True)
    got = _serve(_engine(served, jnp.float32, hf, model), ids)
    assert _gap(got, _want(params, ids)) > LEFT_OUT_MIN


# ------------------------------------------------------------------ #
# (c): the bf16 engine against the float32 reference
# ------------------------------------------------------------------ #
def test_bf16_engine_within_tolerance_and_routing_agreement(routed):
    params = jax.tree.map(
        lambda a: a.astype(jnp.bfloat16).astype(jnp.float32), _params())
    ids = _ids()
    routed.clear()              # the training model's init routes too
    got = _serve(_engine(params, jnp.bfloat16), ids)
    gap = _gap(got, _want(params, ids))
    want = np.sort(reference.routings(family.reference_params(params), ids,
                                      HF), axis=-1)
    agree = float(np.mean(np.all(_real_rows(routed) == want, axis=-1)))
    print(f"bf16 engine: logits gap {gap:.4f}, {100 * agree:.1f}% of "
          f"{want.shape[0] * want.shape[1]} (token, layer) routings agree")
    assert gap <= BF16_TOL
    assert BF16_MIN_ROUTING_AGREEMENT <= agree <= 1.0


# ------------------------------------------------------------------ #
# (d): a mixed tick: one prompt chunk beside decoding sequences
# ------------------------------------------------------------------ #
def test_mixed_tick_rows_of_both_segments():
    params = _params()
    eng = _engine(params, jnp.float32)
    rng = np.random.default_rng(11)
    seqs = {1: rng.integers(0, 256, size=(21,)),
            2: rng.integers(0, 256, size=(9,)),
            3: rng.integers(0, 256, size=(40,))}
    eng.put([1, 2], [seqs[1][:20].tolist(), seqs[2][:8].tolist()])
    # one forward: two single-token rows and a 39-token chunk (three tiles)
    out = eng.put([1, 2, 3], [seqs[1][20:].tolist(), seqs[2][8:].tolist(),
                              seqs[3][:39].tolist()])
    assert eng.step_keys == [(MAX_SEQS + 64, TILE)]     # two-segment
    for uid, n in ((1, 21), (2, 9), (3, 39)):
        want = reference.logits_at(family.reference_params(params),
                                   seqs[uid][:n], HF, rows=[n - 1])[0]
        assert _gap(np.asarray(out[uid], np.float32)[None],
                    want[None]) <= F32_TOL, uid


# ------------------------------------------------------------------ #
# (e): the grouped path at 64 experts against the dense composition
# ------------------------------------------------------------------ #
@pytest.mark.parametrize("interpret, t", [(True, 32), (None, 32), (True, 33)],
                         ids=["gmm_kernel_interpreted", "xla_composition",
                              "rows_padded_to_a_tile"])
def test_grouped_path_matches_dense_at_64_experts(interpret, t, monkeypatch):
    """M = 32 tokens x top-8 = 256 routed rows over 64 experts, the decode
    tick's shape: groups of 0 .. ~12 rows, several of them empty, every
    work unit a boundary unit of the one 256-row tile.  33 tokens are 264
    rows, no whole tile: they are padded to 384 and still go through the
    kernel (chip_smoke's 8-slot engine feeds 520 rows a forward; the
    first chip call of PR 25 found them on the XLA composition)."""
    e, k, h, f = 64, 8, 128, 128
    rng = np.random.default_rng(5)
    moe = {"gate": {"wg": {"kernel": jnp.asarray(
               3.0 * h ** -0.5 * rng.standard_normal((h, e)), jnp.float32)}},
           "experts": {n: jnp.asarray(
               s[1] ** -0.5 * rng.standard_normal(s), jnp.float32)
               for n, s in (("w_gate", (e, h, f)), ("w_up", (e, h, f)),
                            ("w_down", (e, f, h)))}}
    # tokens that favour few experts, so that several groups stay empty
    x = jnp.asarray(rng.standard_normal((t, h)) * 0.2 +
                    rng.standard_normal((1, h)), jnp.float32)
    logits = x @ moe["gate"]["wg"]["kernel"]
    topi, _ = grouped_gemm.exact_topk_routing(logits, k, False)
    sizes = np.bincount(np.asarray(topi).ravel(), minlength=e)
    assert sizes.sum() == t * k and (sizes == 0).sum() >= 3
    if interpret:
        real, kernel = grouped_gemm.grouped_moe_ffn, []
        monkeypatch.setattr(
            grouped_gemm, "grouped_moe_ffn",
            lambda *a, **kw: real(*a, interpret=True, **kw))
        call = grouped_gemm._gmm_fwd_kernel_call
        monkeypatch.setattr(
            grouped_gemm, "_gmm_fwd_kernel_call",
            lambda lhs, *a, **kw: kernel.append(lhs.shape[0]) or
            call(lhs, *a, **kw))
    got = dropless_moe(x, moe, k, jnp.float32, renormalize=False)
    want = dropless_moe(x, moe, k, jnp.float32, grouped=False,
                        renormalize=False)
    # the same float32 products summed in another order
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=1e-4)
    if interpret:       # all three GEMMs went through the kernel
        assert kernel == [-(-t * k // 128) * 128] * 3


# ------------------------------------------------------------------ #
# the training model on the same tree (the two parameter trees are one)
# ------------------------------------------------------------------ #
def test_training_forward_matches_reference():
    # top-3: MixtralBlock routes more than two experts a token through the
    # dropless gate, the one that honours norm_topk_prob
    hf = dict(HF, num_experts_per_tok=3)
    params, ids = _params(hf), _ids(n=40)
    logits = MixtralForCausalLM(_config(jnp.float32, hf)).apply(
        {"params": params}, np.asarray(ids, np.int32)[None], train=False)
    want = reference.logits_at(family.reference_params(params), ids, hf,
                               rows=list(range(len(ids))))
    assert _gap(np.asarray(logits[0], np.float32), want) <= F32_TOL


# ------------------------------------------------------------------ #
# (f): a checkpoint under the published tensor names
# ------------------------------------------------------------------ #
def test_hf_checkpoint_round_trip(tmp_path):
    """A tiny ``OlmoeForCausalLM`` saved by transformers, loaded by name
    (``self_attn.q_norm/k_norm.weight``, ``mlp.gate.weight``,
    ``mlp.experts.<e>.{gate,up,down}_proj.weight`` stacked to [E, ...]),
    served by ``InferenceEngineV2.from_hf``: the engine, the plain
    reference and the published implementation agree."""
    transformers = pytest.importorskip("transformers")
    torch = pytest.importorskip("torch")
    from deepspeed_tpu.checkpoint.hf_loader import (load_hf_checkpoint,
                                                    model_from_hf)

    torch.manual_seed(0)
    hf_cfg = transformers.OlmoeConfig(
        **{k: v for k, v in HF.items() if k != "model_type"})
    hf_model = transformers.OlmoeForCausalLM(hf_cfg).eval()
    with torch.no_grad():
        for name, p in hf_model.named_parameters():
            if "norm" in name:
                p.uniform_(0.5, 1.5)
            elif "mlp.gate" in name:
                p.normal_(0.0, 2.0 * HF["hidden_size"] ** -0.5)
    hf_cfg.save_pretrained(tmp_path)
    hf_model.save_pretrained(tmp_path, safe_serialization=True)

    arch, cfg, _module = model_from_hf(str(tmp_path), dtype=jnp.float32)
    assert arch == "olmoe" and cfg.qk_norm and not cfg.norm_topk_prob
    assert (cfg.num_local_experts, cfg.num_experts_per_tok) == (8, 2)
    params = load_hf_checkpoint(str(tmp_path), dtype=jnp.float32)
    want_shapes = jax.tree.map(lambda a: a.shape,
                               family.serve_param_shapes(HF))
    assert jax.tree.map(lambda a: a.shape, params) == want_shapes

    ids = _ids(seed=9, n=24)
    with torch.no_grad():
        theirs = hf_model(torch.from_numpy(ids[None])).logits.numpy()[0]
    ref = reference.logits_at(family.reference_params(params), ids, HF,
                              rows=list(range(len(ids))))
    assert _gap(ref, theirs) <= F32_TOL
    eng = InferenceEngineV2.from_hf(
        str(tmp_path), RaggedInferenceEngineConfig.from_dict({
            "state_manager": {"max_ragged_batch_size": 32,
                              "max_ragged_sequence_count": 2,
                              "max_context": 64},
            "kv_cache": {"block_size": 8}}), dtype=jnp.float32)
    got = _serve(eng, ids, n_prompt=20)
    assert _gap(got, theirs[19:]) <= F32_TOL
