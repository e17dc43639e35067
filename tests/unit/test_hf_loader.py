"""HF checkpoint ingestion: real (tiny, randomly initialised) HuggingFace
checkpoints saved with ``save_pretrained`` must load into our param trees
and reproduce the HF logits (reference: inference/engine.py:331
``load_model_with_checkpoint`` + module_inject/containers weight maps).

Runs fully on the CPU mesh; transformers/torch execute the reference
forward in fp32 and our flax models are run in fp32 for comparison.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

transformers = pytest.importorskip("transformers")
torch = pytest.importorskip("torch")

from deepspeed_tpu.checkpoint.hf_loader import (  # noqa: E402
    config_from_hf,
    load_hf_checkpoint,
    model_from_hf,
)

ATOL = 2e-4


@pytest.fixture(autouse=True)
def _seed_torch():
    # transformers initialises random weights from torch's global RNG;
    # pin it so every test sees the same checkpoint across runs
    torch.manual_seed(0)


def _save(tmp_path, model, config):
    model.eval()
    config.save_pretrained(tmp_path)
    model.save_pretrained(tmp_path, safe_serialization=True)
    return str(tmp_path)


def _hf_logits(model, ids):
    with torch.no_grad():
        return model(torch.from_numpy(ids)).logits.numpy()


def test_llama_logits_match_hf(tmp_path):
    hf_cfg = transformers.LlamaConfig(
        vocab_size=256, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=128, tie_word_embeddings=False)
    hf = transformers.LlamaForCausalLM(hf_cfg)
    path = _save(tmp_path, hf, hf_cfg)

    arch, cfg, module = model_from_hf(path, dtype=jnp.float32)
    assert arch == "llama" and cfg.num_key_value_heads == 2
    params = load_hf_checkpoint(path, dtype=jnp.float32)
    ids = np.random.default_rng(0).integers(0, 256, size=(2, 12),
                                            dtype=np.int64)
    ours = np.asarray(module.apply({"params": params},
                                   jnp.asarray(ids, jnp.int32)))
    theirs = _hf_logits(hf, ids)
    np.testing.assert_allclose(ours, theirs, atol=ATOL, rtol=1e-3)


def test_mistral_swa_logits_match_hf(tmp_path):
    hf_cfg = transformers.MistralConfig(
        vocab_size=256, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=128, sliding_window=8,
        tie_word_embeddings=False)
    hf = transformers.MistralForCausalLM(hf_cfg)
    path = _save(tmp_path, hf, hf_cfg)

    arch, cfg, module = model_from_hf(path, dtype=jnp.float32)
    assert arch == "mistral" and cfg.sliding_window == 8
    params = load_hf_checkpoint(path, dtype=jnp.float32)
    # seq > window exercises the banded mask on both sides
    ids = np.random.default_rng(1).integers(0, 256, size=(1, 24),
                                            dtype=np.int64)
    ours = np.asarray(module.apply({"params": params},
                                   jnp.asarray(ids, jnp.int32)))
    theirs = _hf_logits(hf, ids)
    np.testing.assert_allclose(ours, theirs, atol=ATOL, rtol=1e-3)


def test_gpt2_logits_match_hf(tmp_path):
    hf_cfg = transformers.GPT2Config(
        vocab_size=256, n_embd=64, n_layer=2, n_head=4, n_positions=128,
        resid_pdrop=0.0, embd_pdrop=0.0, attn_pdrop=0.0)
    hf = transformers.GPT2LMHeadModel(hf_cfg)
    path = _save(tmp_path, hf, hf_cfg)

    arch, cfg, module = model_from_hf(path, dtype=jnp.float32)
    assert arch == "gpt2"
    params = load_hf_checkpoint(path, dtype=jnp.float32)
    ids = np.random.default_rng(2).integers(0, 256, size=(2, 10),
                                            dtype=np.int64)
    ours = np.asarray(module.apply({"params": params},
                                   jnp.asarray(ids, jnp.int32)))
    theirs = _hf_logits(hf, ids)
    np.testing.assert_allclose(ours, theirs, atol=ATOL, rtol=1e-3)


def test_gpt2_nondefault_n_inner_loads_and_matches(tmp_path):
    """Non-default HF ``n_inner`` must reach GPT2Config.intermediate_size
    (same hardcoded-4x shape-error fix as GPT-J)."""
    hf_cfg = transformers.GPT2Config(
        vocab_size=256, n_embd=64, n_layer=2, n_head=4, n_inner=96,
        n_positions=128, resid_pdrop=0.0, embd_pdrop=0.0, attn_pdrop=0.0)
    hf = transformers.GPT2LMHeadModel(hf_cfg)
    path = _save(tmp_path, hf, hf_cfg)

    arch, cfg, module = model_from_hf(path, dtype=jnp.float32)
    assert arch == "gpt2" and cfg.intermediate_size == 96
    params = load_hf_checkpoint(path, dtype=jnp.float32)
    ids = np.random.default_rng(25).integers(0, 256, size=(2, 10),
                                             dtype=np.int64)
    ours = np.asarray(module.apply({"params": params},
                                   jnp.asarray(ids, jnp.int32)))
    theirs = _hf_logits(hf, ids)
    np.testing.assert_allclose(ours, theirs, atol=ATOL, rtol=1e-3)


def test_opt_logits_match_hf(tmp_path):
    hf_cfg = transformers.OPTConfig(
        vocab_size=256, hidden_size=64, ffn_dim=128, num_hidden_layers=2,
        num_attention_heads=4, max_position_embeddings=128,
        do_layer_norm_before=True, word_embed_proj_dim=64)
    hf = transformers.OPTForCausalLM(hf_cfg)
    path = _save(tmp_path, hf, hf_cfg)

    arch, cfg, module = model_from_hf(path, dtype=jnp.float32)
    assert arch == "opt"
    params = load_hf_checkpoint(path, dtype=jnp.float32)
    ids = np.random.default_rng(3).integers(0, 256, size=(2, 9),
                                            dtype=np.int64)
    ours = np.asarray(module.apply({"params": params},
                                   jnp.asarray(ids, jnp.int32)))
    theirs = _hf_logits(hf, ids)
    np.testing.assert_allclose(ours, theirs, atol=ATOL, rtol=1e-3)


def test_mixtral_ragged_engine_matches_hf(tmp_path):
    """Mixtral weights (per-expert tensors stacked onto the grouped-einsum
    layout) through the FastGen ragged engine: the dropless MoE path must
    reproduce HF's exact top-2 routing logits."""
    hf_cfg = transformers.MixtralConfig(
        vocab_size=256, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=128, num_local_experts=4,
        num_experts_per_tok=2, tie_word_embeddings=False)
    hf = transformers.MixtralForCausalLM(hf_cfg)
    path = _save(tmp_path, hf, hf_cfg)

    arch, cfg, _module = model_from_hf(path, dtype=jnp.float32)
    assert arch == "mixtral" and cfg.num_local_experts == 4
    params = load_hf_checkpoint(path, dtype=jnp.float32)

    from deepspeed_tpu.inference.v2 import (InferenceEngineV2,
                                            RaggedInferenceEngineConfig)
    from deepspeed_tpu.inference.v2.model_implementations import (
        RaggedMixtral)

    eng_cfg = RaggedInferenceEngineConfig.from_dict({
        "state_manager": {"max_ragged_batch_size": 16,
                          "max_ragged_sequence_count": 2,
                          "max_context": 32},
        "kv_cache": {"block_size": 8},
    })
    eng = InferenceEngineV2(RaggedMixtral(cfg, 8), params, eng_cfg)
    ids = np.random.default_rng(4).integers(0, 256, size=(1, 10),
                                            dtype=np.int64)
    logits = eng.put([1], [ids[0].tolist()])
    eng.flush([1])
    theirs = _hf_logits(hf, ids)[0, -1]
    np.testing.assert_allclose(logits[1], theirs, atol=5e-4, rtol=1e-3)


def test_falcon_logits_match_hf(tmp_path):
    """Falcon (parallel attention + MQA + fused qkv): our training model
    must reproduce HF logits from a loaded checkpoint."""
    hf_cfg = transformers.FalconConfig(
        vocab_size=256, hidden_size=64, num_hidden_layers=2,
        num_attention_heads=4, multi_query=True, parallel_attn=True,
        new_decoder_architecture=False, bias=False, alibi=False)
    hf = transformers.FalconForCausalLM(hf_cfg)
    path = _save(tmp_path, hf, hf_cfg)

    arch, cfg, module = model_from_hf(path, dtype=jnp.float32)
    assert arch == "falcon" and cfg.num_kv_heads == 1
    params = load_hf_checkpoint(path, dtype=jnp.float32)
    ids = np.random.default_rng(11).integers(0, 256, size=(2, 10),
                                             dtype=np.int64)
    ours = np.asarray(module.apply({"params": params},
                                   jnp.asarray(ids, jnp.int32)))
    theirs = _hf_logits(hf, ids)
    np.testing.assert_allclose(ours, theirs, atol=ATOL, rtol=1e-3)


def _ragged_engine_for(path, dtype=jnp.float32):
    from deepspeed_tpu.inference.v2 import (InferenceEngineV2,
                                            RaggedInferenceEngineConfig)

    eng_cfg = RaggedInferenceEngineConfig.from_dict({
        "state_manager": {"max_ragged_batch_size": 16,
                          "max_ragged_sequence_count": 2,
                          "max_context": 32},
        "kv_cache": {"block_size": 8},
    })
    return InferenceEngineV2.from_hf(path, eng_cfg, dtype=dtype)


@pytest.mark.parametrize("family", ["opt", "falcon"])
def test_v2_opt_falcon_token_parity(tmp_path, family):
    """OPT (learned positions, biases, ReLU) and Falcon (parallel attn,
    MQA) through the ragged engine: prefill logits AND greedy decode
    tokens must match HF transformers (prefill + per-token paths both
    exercise the paged-KV machinery the Llama-shaped code baked
    assumptions into)."""
    if family == "opt":
        hf_cfg = transformers.OPTConfig(
            vocab_size=256, hidden_size=64, ffn_dim=128,
            num_hidden_layers=2, num_attention_heads=4,
            max_position_embeddings=128, do_layer_norm_before=True,
            word_embed_proj_dim=64)
        hf = transformers.OPTForCausalLM(hf_cfg)
    else:
        hf_cfg = transformers.FalconConfig(
            vocab_size=256, hidden_size=64, num_hidden_layers=2,
            num_attention_heads=4, multi_query=True, parallel_attn=True,
            new_decoder_architecture=False, bias=False, alibi=False)
        hf = transformers.FalconForCausalLM(hf_cfg)
    path = _save(tmp_path, hf, hf_cfg)

    eng = _ragged_engine_for(path)
    ids = np.random.default_rng(12).integers(0, 256, size=(1, 10),
                                             dtype=np.int64)
    # prefill logits parity
    logits = eng.put([1], [ids[0].tolist()])
    theirs = _hf_logits(hf, ids)[0, -1]
    np.testing.assert_allclose(logits[1], theirs, atol=5e-4, rtol=1e-3)
    eng.flush([1])

    # greedy generation parity (put -> decode_loop path)
    out = eng.generate([ids[0].tolist()], max_new_tokens=6)
    with torch.no_grad():
        want = hf.generate(torch.from_numpy(ids), max_new_tokens=6,
                           do_sample=False, pad_token_id=0,
                           eos_token_id=None).numpy()[0, 10:]
    np.testing.assert_array_equal(np.asarray(out[0])[:len(want)], want)


def test_presharded_landing(tmp_path):
    """With a mesh, every loaded tensor lands with its policy
    PartitionSpec (column-split q_proj, vocab-split embedding) and the
    sharded forward matches the unsharded one."""
    from jax.sharding import Mesh

    hf_cfg = transformers.LlamaConfig(
        vocab_size=256, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=128, tie_word_embeddings=False)
    hf = transformers.LlamaForCausalLM(hf_cfg)
    path = _save(tmp_path, hf, hf_cfg)

    devs = np.array(jax.devices()[:2]).reshape(2)
    mesh = Mesh(devs, ("model",))
    params = load_hf_checkpoint(path, dtype=jnp.float32, mesh=mesh)
    q = params["model"]["layers_0"]["self_attn"]["q_proj"]["kernel"]
    emb = params["model"]["embed_tokens"]["embedding"]
    assert q.sharding.spec == jax.sharding.PartitionSpec(None, "model")
    assert emb.sharding.spec == jax.sharding.PartitionSpec("model", None)
    # the sharded tree computes the same logits
    _arch, _cfg, module = model_from_hf(path, dtype=jnp.float32)
    ref = load_hf_checkpoint(path, dtype=jnp.float32)
    ids = jnp.asarray(np.random.default_rng(5).integers(
        0, 256, size=(1, 8)), jnp.int32)
    np.testing.assert_allclose(
        np.asarray(module.apply({"params": params}, ids)),
        np.asarray(module.apply({"params": ref}, ids)), atol=1e-5)


def test_v2_engine_from_hf_matches_hf_greedy(tmp_path):
    """FastGen InferenceEngineV2.from_hf: generate() greedy tokens match
    HF transformers generation token-for-token (north-star path: a real
    checkpoint served through the ragged engine)."""
    hf_cfg = transformers.LlamaConfig(
        vocab_size=256, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=128, tie_word_embeddings=False)
    hf = transformers.LlamaForCausalLM(hf_cfg)
    path = _save(tmp_path, hf, hf_cfg)

    from deepspeed_tpu.inference.v2 import (InferenceEngineV2,
                                            RaggedInferenceEngineConfig)

    eng_cfg = RaggedInferenceEngineConfig.from_dict({
        "state_manager": {"max_ragged_batch_size": 16,
                          "max_ragged_sequence_count": 2,
                          "max_context": 32},
        "kv_cache": {"block_size": 8},
    })
    eng = InferenceEngineV2.from_hf(path, eng_cfg, dtype=jnp.float32)
    ids = np.random.default_rng(7).integers(0, 256, size=(1, 8),
                                            dtype=np.int64)
    out = eng.generate([ids[0].tolist()], max_new_tokens=8)
    with torch.no_grad():
        theirs = hf.generate(
            torch.from_numpy(ids), max_new_tokens=8, do_sample=False,
            pad_token_id=0).numpy()[0, 8:]
    # HF generate() early-stops at its eos_token_id; ours was not given
    # one — compare the prefix HF actually produced
    assert len(theirs) >= 1
    np.testing.assert_array_equal(np.asarray(out[0])[:len(theirs)], theirs)


def test_v1_engine_generate_from_hf(tmp_path):
    """init_inference(checkpoint=hf_dir) end-to-end: greedy generate()
    must match HF transformers' greedy generation token-for-token."""
    hf_cfg = transformers.LlamaConfig(
        vocab_size=256, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=128, tie_word_embeddings=False)
    hf = transformers.LlamaForCausalLM(hf_cfg)
    path = _save(tmp_path, hf, hf_cfg)

    import deepspeed_tpu

    eng = deepspeed_tpu.init_inference(checkpoint=path,
                                       config={"dtype": jnp.float32})
    ids = np.random.default_rng(6).integers(0, 256, size=(1, 8),
                                            dtype=np.int64)
    ours = np.asarray(eng.generate(jnp.asarray(ids, jnp.int32),
                                   max_new_tokens=8))
    with torch.no_grad():
        theirs = hf.generate(
            torch.from_numpy(ids), max_new_tokens=8, do_sample=False,
            pad_token_id=0).numpy()
    np.testing.assert_array_equal(ours[:, :theirs.shape[1]], theirs)


def test_v2_opt_rejects_context_past_position_table(tmp_path):
    """OPT's learned position table bounds max_context — exceeding it
    must fail at engine construction, not silently alias positions."""
    hf_cfg = transformers.OPTConfig(
        vocab_size=256, hidden_size=64, ffn_dim=128, num_hidden_layers=2,
        num_attention_heads=4, max_position_embeddings=16,
        do_layer_norm_before=True, word_embed_proj_dim=64)
    hf = transformers.OPTForCausalLM(hf_cfg)
    path = _save(tmp_path, hf, hf_cfg)

    from deepspeed_tpu.inference.v2 import (InferenceEngineV2,
                                            RaggedInferenceEngineConfig)

    eng_cfg = RaggedInferenceEngineConfig.from_dict({
        "state_manager": {"max_ragged_batch_size": 16,
                          "max_ragged_sequence_count": 2,
                          "max_context": 32},  # > 16-position table
        "kv_cache": {"block_size": 8},
    })
    with pytest.raises(ValueError, match="position table"):
        InferenceEngineV2.from_hf(path, eng_cfg, dtype=jnp.float32)


def test_bloom_logits_match_hf(tmp_path):
    """BLOOM (ALiBi bias, per-head fused qkv interleave, embedding
    LayerNorm, tanh GELU): our model must reproduce HF logits."""
    hf_cfg = transformers.BloomConfig(
        vocab_size=256, hidden_size=64, n_layer=2, n_head=4,
        hidden_dropout=0.0, attention_dropout=0.0)
    hf = transformers.BloomForCausalLM(hf_cfg)
    path = _save(tmp_path, hf, hf_cfg)

    arch, cfg, module = model_from_hf(path, dtype=jnp.float32)
    assert arch == "bloom" and cfg.num_attention_heads == 4
    params = load_hf_checkpoint(path, dtype=jnp.float32)
    ids = np.random.default_rng(20).integers(0, 256, size=(2, 11),
                                             dtype=np.int64)
    ours = np.asarray(module.apply({"params": params},
                                   jnp.asarray(ids, jnp.int32)))
    theirs = _hf_logits(hf, ids)
    np.testing.assert_allclose(ours, theirs, atol=ATOL, rtol=1e-3)


def test_bloom_nonpow2_heads_logits_match_hf(tmp_path):
    """Non-power-of-2 head count exercises the two-series ALiBi slope
    interleave."""
    hf_cfg = transformers.BloomConfig(
        vocab_size=256, hidden_size=96, n_layer=1, n_head=6,
        hidden_dropout=0.0, attention_dropout=0.0)
    hf = transformers.BloomForCausalLM(hf_cfg)
    path = _save(tmp_path, hf, hf_cfg)

    _arch, _cfg, module = model_from_hf(path, dtype=jnp.float32)
    params = load_hf_checkpoint(path, dtype=jnp.float32)
    ids = np.random.default_rng(21).integers(0, 256, size=(1, 9),
                                             dtype=np.int64)
    ours = np.asarray(module.apply({"params": params},
                                   jnp.asarray(ids, jnp.int32)))
    theirs = _hf_logits(hf, ids)
    np.testing.assert_allclose(ours, theirs, atol=ATOL, rtol=1e-3)


def test_gptj_logits_match_hf(tmp_path):
    """GPT-J (parallel residual, bias-free attention, INTERLEAVED partial
    rotary, biased untied lm_head)."""
    hf_cfg = transformers.GPTJConfig(
        vocab_size=256, n_embd=64, n_layer=2, n_head=4, rotary_dim=8,
        n_positions=128, resid_pdrop=0.0, embd_pdrop=0.0, attn_pdrop=0.0)
    hf = transformers.GPTJForCausalLM(hf_cfg)
    path = _save(tmp_path, hf, hf_cfg)

    arch, cfg, module = model_from_hf(path, dtype=jnp.float32)
    assert arch == "gptj" and cfg.rotary_dim == 8
    params = load_hf_checkpoint(path, dtype=jnp.float32)
    ids = np.random.default_rng(22).integers(0, 256, size=(2, 13),
                                             dtype=np.int64)
    ours = np.asarray(module.apply({"params": params},
                                   jnp.asarray(ids, jnp.int32)))
    theirs = _hf_logits(hf, ids)
    np.testing.assert_allclose(ours, theirs, atol=ATOL, rtol=1e-3)


def test_gptj_nondefault_n_inner_loads_and_matches(tmp_path):
    """HF ``n_inner`` (non-default MLP width) must reach
    GPTJConfig.intermediate_size — previously the 4x width was hardcoded
    and such checkpoints shape-errored on fc_in."""
    hf_cfg = transformers.GPTJConfig(
        vocab_size=256, n_embd=64, n_layer=2, n_head=4, rotary_dim=8,
        n_inner=96, n_positions=128, resid_pdrop=0.0, embd_pdrop=0.0,
        attn_pdrop=0.0)
    hf = transformers.GPTJForCausalLM(hf_cfg)
    path = _save(tmp_path, hf, hf_cfg)

    arch, cfg, module = model_from_hf(path, dtype=jnp.float32)
    assert arch == "gptj" and cfg.intermediate_size == 96
    params = load_hf_checkpoint(path, dtype=jnp.float32)
    ids = np.random.default_rng(24).integers(0, 256, size=(2, 11),
                                             dtype=np.int64)
    ours = np.asarray(module.apply({"params": params},
                                   jnp.asarray(ids, jnp.int32)))
    theirs = _hf_logits(hf, ids)
    np.testing.assert_allclose(ours, theirs, atol=ATOL, rtol=1e-3)


@pytest.mark.parametrize("parallel", [True, False])
def test_gptneox_logits_match_hf(tmp_path, parallel):
    """GPT-NeoX (per-head fused qkv, partial half-split rotary, parallel
    and sequential residual variants, untied embed_out)."""
    hf_cfg = transformers.GPTNeoXConfig(
        vocab_size=256, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, rotary_pct=0.5,
        max_position_embeddings=128, use_parallel_residual=parallel,
        hidden_dropout=0.0, attention_dropout=0.0)
    hf = transformers.GPTNeoXForCausalLM(hf_cfg)
    path = _save(tmp_path, hf, hf_cfg)

    arch, cfg, module = model_from_hf(path, dtype=jnp.float32)
    assert arch in ("gpt_neox", "gptneox")
    assert cfg.rotary_ndims == 8 and cfg.use_parallel_residual == parallel
    params = load_hf_checkpoint(path, dtype=jnp.float32)
    ids = np.random.default_rng(23).integers(0, 256, size=(2, 10),
                                             dtype=np.int64)
    ours = np.asarray(module.apply({"params": params},
                                   jnp.asarray(ids, jnp.int32)))
    theirs = _hf_logits(hf, ids)
    np.testing.assert_allclose(ours, theirs, atol=ATOL, rtol=1e-3)


def test_bert_hidden_states_match_hf(tmp_path):
    """BERT encoder (post-norm residuals, token-type + learned positions,
    tanh pooler): last_hidden_state AND pooler_output must match HF."""
    hf_cfg = transformers.BertConfig(
        vocab_size=256, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4,
        max_position_embeddings=128, type_vocab_size=2,
        hidden_dropout_prob=0.0, attention_probs_dropout_prob=0.0)
    hf = transformers.BertModel(hf_cfg)
    path = _save(tmp_path, hf, hf_cfg)

    arch, cfg, module = model_from_hf(path, dtype=jnp.float32)
    assert arch == "bert"
    params = load_hf_checkpoint(path, dtype=jnp.float32)
    rng = np.random.default_rng(24)
    ids = rng.integers(0, 256, size=(2, 12), dtype=np.int64)
    type_ids = rng.integers(0, 2, size=(2, 12), dtype=np.int64)
    hidden, pooled = module.apply(
        {"params": params}, jnp.asarray(ids, jnp.int32),
        jnp.asarray(type_ids, jnp.int32))
    with torch.no_grad():
        out = hf(torch.from_numpy(ids),
                 token_type_ids=torch.from_numpy(type_ids))
    np.testing.assert_allclose(np.asarray(hidden),
                               out.last_hidden_state.numpy(),
                               atol=ATOL, rtol=1e-3)
    np.testing.assert_allclose(np.asarray(pooled),
                               out.pooler_output.numpy(),
                               atol=ATOL, rtol=1e-3)


# ------------------------------------------------------------------ #
# Jamba (dense): no transformers class is needed, the checkpoint is a
# synthetic state dict under the published tensor names
# ------------------------------------------------------------------ #
JAMBA = {"model_type": "jamba", "vocab_size": 128, "hidden_size": 32,
         "intermediate_size": 48, "num_hidden_layers": 3,
         "num_attention_heads": 2, "num_key_value_heads": 1,
         "attn_layer_period": 3, "attn_layer_offset": 1, "mamba_expand": 2,
         "mamba_d_state": 4, "mamba_d_conv": 4, "mamba_dt_rank": 6,
         "mamba_conv_bias": True, "mamba_proj_bias": False,
         "num_experts": 1, "num_experts_per_tok": 1, "rms_norm_eps": 1e-6,
         "max_position_embeddings": 256, "tie_word_embeddings": True,
         "sliding_window": None}


def test_jamba_rules_on_a_synthetic_state_dict(tmp_path):
    """Tensors named and laid out as the published checkpoint has them
    ([out, in] matrices, ``conv1d.weight`` [channels, 1, taps] with its
    bias, ``A_log`` [channels, states], ``D``, the three inner norms,
    ``feed_forward.*``, a tied ``lm_head.weight`` beside the embedding)
    load into ``RaggedJamba``'s tree, and the engine built from the
    directory serves the plain reference's logits, the reference fed the
    same tensors by their published meaning."""
    import json
    import os
    import sys

    from safetensors.numpy import save_file

    repo = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    if repo not in sys.path:
        sys.path.insert(0, repo)
    from benchmark.reference import jamba as reference
    from deepspeed_tpu.inference.v2 import (InferenceEngineV2,
                                            RaggedInferenceEngineConfig)
    from deepspeed_tpu.inference.v2.model_implementations import \
        ragged_jamba as rj

    rng = np.random.default_rng(8)
    h, f, di, n, r = 32, 48, 64, 4, 6
    g = lambda *s: rng.standard_normal(s).astype(np.float32)
    norm = lambda w: rng.uniform(0.5, 1.5, w).astype(np.float32)
    sd = {"model.embed_tokens.weight": g(128, h),
          "model.final_layernorm.weight": norm(h)}
    sd["lm_head.weight"] = sd["model.embed_tokens.weight"]
    ref_layers = []
    for i in range(3):
        pre = f"model.layers.{i}."
        sd[pre + "input_layernorm.weight"] = norm(h)
        sd[pre + "pre_ff_layernorm.weight"] = norm(h)
        for name, shape in (("gate", (f, h)), ("up", (f, h)),
                            ("down", (h, f))):
            sd[pre + f"feed_forward.{name}_proj.weight"] = \
                g(*shape) * shape[1] ** -0.5
        lp = {"ln1": sd[pre + "input_layernorm.weight"],
              "ln2": sd[pre + "pre_ff_layernorm.weight"],
              **{k: sd[pre + f"feed_forward.{k}_proj.weight"].T
                 for k in ("gate", "up", "down")}}
        if i == 1:
            for name, rows in (("q", 32), ("k", 16), ("v", 16)):
                sd[pre + f"self_attn.{name}_proj.weight"] = \
                    g(rows, h) * h ** -0.5
            sd[pre + "self_attn.o_proj.weight"] = g(h, 32) * 32 ** -0.5
            lp.update({f"w{k}": sd[pre + f"self_attn.{k}_proj.weight"].T
                       for k in "qkvo"})
        else:
            m = pre + "mamba."
            sd[m + "in_proj.weight"] = g(2 * di, h) * h ** -0.5
            sd[m + "conv1d.weight"] = g(di, 1, 4) * 0.5
            sd[m + "conv1d.bias"] = g(di)
            sd[m + "x_proj.weight"] = g(r + 2 * n, di) * di ** -0.5
            sd[m + "dt_proj.weight"] = g(di, r) * r ** -0.5
            sd[m + "dt_proj.bias"] = -3.0 + 0.5 * g(di)
            sd[m + "A_log"] = np.log(rng.uniform(1, 4, (di, n))).astype(
                np.float32)
            sd[m + "D"] = g(di)
            sd[m + "out_proj.weight"] = g(h, di) * di ** -0.5
            for k, w in (("dt", r), ("b", n), ("c", n)):
                sd[m + f"{k}_layernorm.weight"] = norm(w)
            lp.update(
                w_in=sd[m + "in_proj.weight"].T,
                taps=sd[m + "conv1d.weight"][:, 0, :].T,
                conv_bias=sd[m + "conv1d.bias"],
                w_x=sd[m + "x_proj.weight"].T,
                w_dt=sd[m + "dt_proj.weight"].T, b_dt=sd[m + "dt_proj.bias"],
                A_log=sd[m + "A_log"], D=sd[m + "D"],
                g_dt=sd[m + "dt_layernorm.weight"],
                g_b=sd[m + "b_layernorm.weight"],
                g_c=sd[m + "c_layernorm.weight"],
                w_out=sd[m + "out_proj.weight"].T)
        ref_layers.append(lp)
    save_file(sd, str(tmp_path / "model.safetensors"))
    with open(tmp_path / "config.json", "w") as fh:
        json.dump(JAMBA, fh)

    arch, cfg = config_from_hf(str(tmp_path), jnp.float32)
    assert arch == "jamba" and type(cfg) is rj.JambaConfig
    params = load_hf_checkpoint(str(tmp_path), dtype=jnp.float32)
    assert jax.tree.map(lambda a: a.shape, params) == jax.tree.map(
        lambda a: a.shape, rj.param_shapes(cfg))
    assert "lm_head" not in params                  # tied: the embedding
    assert params["layers_0"]["mamba"]["A_log"].shape == (n, di)

    eng = InferenceEngineV2.from_hf(
        str(tmp_path), RaggedInferenceEngineConfig.from_dict({
            "state_manager": {"max_ragged_batch_size": 128,
                              "max_ragged_sequence_count": 2,
                              "max_context": 256},
            "kv_cache": {"block_size": 8, "num_blocks": 40}}),
        dtype=jnp.float32)
    assert type(eng.model) is rj.RaggedJamba
    ids = rng.integers(0, 128, size=(150 + 3,))
    got = [np.asarray(eng.put([1], [ids[:150].tolist()])[1], np.float32)]
    for t in ids[150:]:
        got.append(np.asarray(jax.device_get(
            eng.decode_step([1], [int(t)])), np.float32)[0])
    ref = jax.tree.map(jnp.asarray, {
        "embed": sd["model.embed_tokens.weight"], "layers": ref_layers,
        "norm": sd["model.final_layernorm.weight"]})
    want = reference.logits_at(ref, ids, JAMBA, rows=list(range(149, 153)))
    assert np.max(np.abs(np.stack(got) - want)) / np.max(np.abs(want)) < 1e-4


def test_jamba_with_routed_experts_is_refused_by_name(tmp_path):
    import json

    from deepspeed_tpu.checkpoint.hf_loader import HFLoadError

    with open(tmp_path / "config.json", "w") as fh:
        json.dump({**JAMBA, "num_experts": 16, "num_experts_per_tok": 2}, fh)
    with pytest.raises(HFLoadError, match="num_experts=16"):
        config_from_hf(str(tmp_path))


# ------------------------------------------------------------------ #
# Granite-4.0-H: a synthetic state dict under the published tensor names
# ------------------------------------------------------------------ #
GRANITE = {"model_type": "granitemoehybrid", "vocab_size": 128,
           "hidden_size": 32, "intermediate_size": 16,
           "shared_intermediate_size": 24, "num_hidden_layers": 3,
           "layer_types": ["mamba", "attention", "mamba"],
           "num_attention_heads": 2, "num_key_value_heads": 1,
           "mamba_n_heads": 4, "mamba_d_head": 16, "mamba_d_state": 8,
           "mamba_n_groups": 1, "mamba_d_conv": 4, "mamba_expand": 2,
           "mamba_conv_bias": True, "mamba_proj_bias": False,
           "mamba_chunk_size": 256, "attention_bias": False,
           "position_embedding_type": "nope", "num_local_experts": 6,
           "num_experts_per_tok": 2, "embedding_multiplier": 12,
           "residual_multiplier": 0.22, "attention_multiplier": 0.0078125,
           "logits_scaling": 16, "rms_norm_eps": 1e-5,
           "max_position_embeddings": 256, "tie_word_embeddings": True,
           "hidden_act": "silu", "normalization_function": "rmsnorm",
           "rope_theta": 10000, "rope_scaling": None}


def test_granitemoehybrid_rules_on_a_synthetic_state_dict(tmp_path):
    """Tensors named and laid out as the published checkpoint has them
    ([out, in] matrices, ``mamba.in_proj`` rows ``z | xBC | dt``,
    ``conv1d.weight`` [channels, 1, taps] with its bias, ``dt_bias`` /
    ``A_log`` / ``D`` a head, the gated norm, ``block_sparse_moe.
    input_linear`` [E, 2 F, H] with the gate's rows first, ``output_linear``
    [E, H, F], ``router.layer``, ``shared_mlp``, a tied ``lm_head.weight``)
    load into ``RaggedGraniteMoeHybrid``'s tree, and the engine built from
    the directory serves the plain reference's logits, the reference fed
    the same tensors by their published meaning."""
    import json
    import os
    import sys

    from safetensors.numpy import save_file

    repo = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    if repo not in sys.path:
        sys.path.insert(0, repo)
    from benchmark.reference import granite_moe_hybrid as reference
    from deepspeed_tpu.inference.v2 import (InferenceEngineV2,
                                            RaggedInferenceEngineConfig)
    from deepspeed_tpu.inference.v2.model_implementations import \
        ragged_granite_moe_hybrid as rg

    rng = np.random.default_rng(9)
    h, f, fs, di, n, hm, e = 32, 16, 24, 64, 8, 4, 6
    g = lambda *s: rng.standard_normal(s).astype(np.float32)
    norm = lambda w: rng.uniform(0.5, 1.5, w).astype(np.float32)
    sd = {"model.embed_tokens.weight": 0.1 * g(128, h),
          "model.norm.weight": norm(h)}
    sd["lm_head.weight"] = sd["model.embed_tokens.weight"]
    ref_layers = []
    for i in range(3):
        pre = f"model.layers.{i}."
        sd[pre + "input_layernorm.weight"] = norm(h)
        sd[pre + "post_attention_layernorm.weight"] = norm(h)
        moe = pre + "block_sparse_moe."
        sd[moe + "input_linear.weight"] = g(e, 2 * f, h) * h ** -0.5
        sd[moe + "output_linear.weight"] = g(e, h, f) * f ** -0.5
        sd[moe + "router.layer.weight"] = g(e, h) * h ** -0.5
        sd[pre + "shared_mlp.input_linear.weight"] = g(2 * fs, h) * h ** -0.5
        sd[pre + "shared_mlp.output_linear.weight"] = g(h, fs) * fs ** -0.5
        w_in, s_in = sd[moe + "input_linear.weight"], \
            sd[pre + "shared_mlp.input_linear.weight"]
        lp = {"ln1": sd[pre + "input_layernorm.weight"],
              "ln2": sd[pre + "post_attention_layernorm.weight"],
              "router": sd[moe + "router.layer.weight"].T,
              "w_gate": w_in[:, :f].transpose(0, 2, 1),
              "w_up": w_in[:, f:].transpose(0, 2, 1),
              "w_down": sd[moe + "output_linear.weight"].transpose(0, 2, 1),
              "s_gate": s_in[:fs].T, "s_up": s_in[fs:].T,
              "s_down": sd[pre + "shared_mlp.output_linear.weight"].T}
        if i == 1:
            for name, rows in (("q", 32), ("k", 16), ("v", 16)):
                sd[pre + f"self_attn.{name}_proj.weight"] = \
                    (6.0 if name in "qk" else 1.0) * g(rows, h) * h ** -0.5
            sd[pre + "self_attn.o_proj.weight"] = g(h, 32) * 32 ** -0.5
            lp.update({f"w{k}": sd[pre + f"self_attn.{k}_proj.weight"].T
                       for k in "qkvo"})
        else:
            m = pre + "mamba."
            sd[m + "in_proj.weight"] = g(2 * di + 2 * n + hm, h) * h ** -0.5
            sd[m + "conv1d.weight"] = g(di + 2 * n, 1, 4) * 0.5
            sd[m + "conv1d.bias"] = g(di + 2 * n)
            sd[m + "dt_bias"] = -3.0 + 0.5 * g(hm)
            sd[m + "A_log"] = np.log(rng.uniform(0.25, 4, (hm,))).astype(
                np.float32)
            sd[m + "D"] = g(hm)
            sd[m + "norm.weight"] = norm(di)
            sd[m + "out_proj.weight"] = g(h, di) * di ** -0.5
            lp.update(
                w_in=sd[m + "in_proj.weight"].T,
                taps=sd[m + "conv1d.weight"][:, 0, :].T,
                conv_bias=sd[m + "conv1d.bias"], dt_bias=sd[m + "dt_bias"],
                A_log=sd[m + "A_log"], D=sd[m + "D"],
                gnorm=sd[m + "norm.weight"],
                w_out=sd[m + "out_proj.weight"].T)
        ref_layers.append(lp)
    save_file(sd, str(tmp_path / "model.safetensors"))
    with open(tmp_path / "config.json", "w") as fh:
        json.dump(GRANITE, fh)

    arch, cfg = config_from_hf(str(tmp_path), jnp.float32)
    assert arch == "granitemoehybrid" \
        and type(cfg) is rg.GraniteMoeHybridConfig
    assert cfg.layer_types == ("mamba", "attention", "mamba")
    assert (cfg.held_experts, cfg.num_local_experts) == (None, 6)
    params = load_hf_checkpoint(str(tmp_path), dtype=jnp.float32)
    assert jax.tree.map(lambda a: a.shape, params) == jax.tree.map(
        lambda a: a.shape, rg.param_shapes(cfg))
    assert "lm_head" not in params                  # tied: the embedding
    assert params["layers_0"]["block_sparse_moe"]["experts"][
        "w_gate"].shape == (e, h, f)

    eng = InferenceEngineV2.from_hf(
        str(tmp_path), RaggedInferenceEngineConfig.from_dict({
            "state_manager": {"max_ragged_batch_size": 128,
                              "max_ragged_sequence_count": 2,
                              "max_context": 256},
            "kv_cache": {"block_size": 8, "num_blocks": 40}}),
        dtype=jnp.float32)
    assert type(eng.model) is rg.RaggedGraniteMoeHybrid
    ids = rng.integers(0, 128, size=(150 + 3,))
    got = [np.asarray(eng.put([1], [ids[:150].tolist()])[1], np.float32)]
    for t in ids[150:]:
        got.append(np.asarray(jax.device_get(
            eng.decode_step([1], [int(t)])), np.float32)[0])
    ref = jax.tree.map(jnp.asarray, {
        "embed": sd["model.embed_tokens.weight"], "layers": ref_layers,
        "norm": sd["model.norm.weight"]})
    want = reference.logits_at(ref, ids, GRANITE, rows=list(range(149, 153)))
    assert np.max(np.abs(np.stack(got) - want)) / np.max(np.abs(want)) < 1e-4


def test_granitemoehybrid_with_groups_is_refused_by_name(tmp_path):
    import json

    with open(tmp_path / "config.json", "w") as fh:
        json.dump({**GRANITE, "mamba_n_groups": 4}, fh)
    with pytest.raises(NotImplementedError, match="mamba_n_groups=4"):
        config_from_hf(str(tmp_path))


DOTS3 = {"model_type": "dots3_note", "vocab_size": 128, "hidden_size": 32,
         "intermediate_size": 48, "moe_intermediate_size": 16,
         "num_hidden_layers": 3,
         "layer_types": ["full_attention", "sliding_attention",
                         "full_attention"],
         "num_attention_heads": 4, "num_key_value_heads": 4,
         "kv_lora_rank": 16, "q_lora_rank": 24, "qk_nope_head_dim": 8,
         "qk_rope_head_dim": 8, "v_head_dim": 8, "rope_theta": 80000000,
         "index_n_heads": 2, "index_head_dim": 128, "index_topk": 12,
         "attention_gate_type": "headwise",
         "swa_num_attention_heads": 2, "swa_num_key_value_heads": 2,
         "swa_kv_lora_rank": 32, "swa_q_lora_rank": 20,
         "swa_qk_nope_head_dim": 12, "swa_qk_rope_head_dim": 4,
         "swa_v_head_dim": 8, "swa_rope_theta": 50000,
         "swa_attention_gate_type": "headwise", "sliding_window_size": 21,
         "apply_mla_qkv_lora_rescale": True, "n_routed_experts": 4,
         "n_shared_experts": 1, "num_experts_per_tok": 2,
         "first_k_dense_replace": 1, "moe_layer_freq": 1,
         "norm_topk_prob": True, "routed_scaling_factor": 1,
         "scoring_func": "sigmoid", "topk_method": "noaux_tc",
         "attention_bias": False, "hidden_act": "silu",
         "rms_norm_eps": 1e-5, "rope_scaling": None,
         "max_position_embeddings": 256, "tie_word_embeddings": False}


def _dots3_state_dict(cfg, params):
    """``params`` (the program's tree) under the published names and
    layouts: [out, in] matrices, the rope rows of ``q_b_proj`` (a head's
    LAST dims), ``kv_a_proj_with_mqa`` (its last) and the indexer's two
    (a head's FIRST) interleaved, experts one tensor each."""
    def interleave(kernel, width, rope, first=False):
        w = np.asarray(kernel).T                        # [out, in]
        at = 0 if first else width - rope
        order = np.arange(width)
        order[at:at + rope] = at + np.concatenate(
            [np.arange(0, rope, 2), np.arange(1, rope, 2)])
        blocks = w.reshape(-1, width, w.shape[-1])
        out = np.empty_like(blocks)
        out[:, order] = blocks
        return out.reshape(w.shape)

    t = lambda leaf: np.asarray(leaf["kernel"]).T
    sd = {"model.embed_tokens.weight":
          np.asarray(params["embed_tokens"]["embedding"]),
          "model.norm.weight": np.asarray(params["norm"]["scale"]),
          "lm_head.weight": t(params["lm_head"])}
    for i, kind in enumerate(cfg["layer_types"]):
        lp, pre = params[f"layers_{i}"], f"model.layers.{i}."
        sw = "swa_" if kind == "sliding_attention" else ""
        nope, rope, rank = (cfg[sw + k] for k in (
            "qk_nope_head_dim", "qk_rope_head_dim", "kv_lora_rank"))
        att, a = lp["self_attn"], pre + "self_attn."
        sd[pre + "input_layernorm.weight"] = np.asarray(
            lp["input_layernorm"]["scale"])
        sd[pre + "post_attention_layernorm.weight"] = np.asarray(
            lp["post_attention_layernorm"]["scale"])
        sd[a + "q_a_proj.weight"] = t(att["q_a_proj"])
        sd[a + "q_a_layernorm.weight"] = np.asarray(
            att["q_a_layernorm"]["scale"])
        sd[a + "q_b_proj.weight"] = interleave(
            att["q_b_proj"]["kernel"], nope + rope, rope)
        sd[a + "kv_a_proj_with_mqa.weight"] = interleave(
            att["kv_a_proj_with_mqa"]["kernel"], rank + rope, rope)
        sd[a + "kv_a_layernorm.weight"] = np.asarray(
            att["kv_a_layernorm"]["scale"])
        for name in ("kv_b_proj", "o_proj", "gate_proj"):
            sd[a + name + ".weight"] = t(att[name])
        if "indexer" in att:
            ix, full_rope = att["indexer"], cfg["qk_rope_head_dim"]
            for name in ("wq_b", "wk"):
                sd[a + f"indexer.{name}.weight"] = interleave(
                    ix[name]["kernel"], cfg["index_head_dim"], full_rope,
                    first=True)
            sd[a + "indexer.k_norm.weight"] = np.asarray(
                ix["k_norm"]["scale"])
            sd[a + "indexer.k_norm.bias"] = np.asarray(ix["k_norm"]["bias"])
            sd[a + "indexer.weights_proj.weight"] = t(ix["weights_proj"])
        mlp, m = lp["mlp"], pre + "mlp."
        if "gate" not in mlp:
            for name in ("gate", "up", "down"):
                sd[m + f"{name}_proj.weight"] = t(mlp[f"{name}_proj"])
            continue
        sd[m + "gate.weight"] = t(mlp["gate"]["wg"])
        sd[m + "gate.e_score_correction_bias"] = np.asarray(
            mlp["gate"]["e_score_correction_bias"])
        for name in ("gate", "up", "down"):
            sd[m + f"shared_experts.{name}_proj.weight"] = t(
                mlp["shared_expert"][f"{name}_proj"])
            for e in range(cfg["n_routed_experts"]):
                sd[m + f"experts.{e}.{name}_proj.weight"] = np.asarray(
                    mlp["experts"][f"w_{name}"][e]).T
    return {k: np.ascontiguousarray(v, np.float32) for k, v in sd.items()}


def _dots3_checkpoint(tmp_path, extra=None):
    import json

    from safetensors.numpy import save_file

    from deepspeed_tpu.inference.v2.model_implementations import \
        ragged_dots3_note as rd

    _arch_cfg = rd.Dots3NoteConfig(
        **{k: v for k, v in DOTS3.items()
           if k in rd.Dots3NoteConfig.__dataclass_fields__},
        dtype=jnp.float32)
    rng = np.random.default_rng(13)
    params = jax.tree.map(
        lambda l: jnp.asarray(rng.standard_normal(l.shape), jnp.float32),
        rd.param_shapes(_arch_cfg))
    sd = _dots3_state_dict(DOTS3, params)
    sd.update(extra or {})
    save_file(sd, str(tmp_path / "model.safetensors"))
    with open(tmp_path / "config.json", "w") as fh:
        json.dump(DOTS3, fh)
    return params


def test_dots3_note_rules_on_a_seeded_tree(tmp_path):
    """A seeded program tree written out under the published names and
    layouts (each layer's rope rows interleaved at its OWN kind's widths)
    loads back into the same tree, value for value, and the engine built
    from the directory is a ``RaggedDots3Note`` with both pools."""
    from deepspeed_tpu.inference.v2 import (InferenceEngineV2,
                                            RaggedInferenceEngineConfig)
    from deepspeed_tpu.inference.v2.model_implementations import \
        ragged_dots3_note as rd

    params = _dots3_checkpoint(tmp_path)
    arch, cfg = config_from_hf(str(tmp_path), jnp.float32)
    assert arch == "dots3_note" and type(cfg) is rd.Dots3NoteConfig
    assert cfg.layer_types == DOTS3["layer_types"]
    assert (cfg.swa_kv_lora_rank, cfg.sliding_window_size,
            cfg.swa_rope_theta, cfg.rope_theta) == (32, 21, 50000, 80000000)
    assert cfg.apply_mla_qkv_lora_rescale and cfg.held_experts is None
    assert (cfg.attention_gate_type, cfg.swa_attention_gate_type) \
        == ("headwise", "headwise")
    loaded = load_hf_checkpoint(str(tmp_path), dtype=jnp.float32)
    assert jax.tree.structure(loaded) == jax.tree.structure(params)
    same = jax.tree.map(lambda a, b: bool(jnp.array_equal(a, b)), loaded,
                        params)
    assert all(jax.tree.leaves(same)), same
    eng = InferenceEngineV2.from_hf(
        str(tmp_path), RaggedInferenceEngineConfig.from_dict({
            "state_manager": {"max_ragged_batch_size": 64,
                              "max_ragged_sequence_count": 2,
                              "max_context": 128},
            "kv_cache": {"block_size": 8, "num_blocks": 40}}),
        dtype=jnp.float32)
    assert type(eng.model) is rd.RaggedDots3Note
    kv = eng.state_manager.kv_cache
    assert kv.window_layers == (1,) and kv.window_row == {"ckv": 128}
    logits = eng.put([1], [list(range(40))])[1]
    assert np.isfinite(np.asarray(logits)).all()


@pytest.mark.parametrize("name, what", [
    ("visual.blocks.0.attn.qkv.weight", "the vision tower"),
    ("model.vision_tower.patch_embed.proj.weight", "the vision tower"),
    ("audio_tower.conv1.weight", "the audio encoder"),
    ("model.layers.3.eh_proj.weight", "multi-token prediction"),
    ("mtp.layers.0.input_layernorm.weight", "multi-token prediction"),
    ("model.layers.3.input_layernorm.weight", "past num_hidden_layers"),
])
def test_dots3_note_towers_and_mtp_are_refused_by_name(tmp_path, name, what):
    from deepspeed_tpu.checkpoint.hf_loader import HFLoadError

    _dots3_checkpoint(tmp_path, {name: np.zeros((32,), np.float32)})
    with pytest.raises(HFLoadError, match=what):
        load_hf_checkpoint(str(tmp_path), dtype=jnp.float32)


def test_dots3_note_gate_types_are_refused_by_name(tmp_path):
    import json

    with open(tmp_path / "config.json", "w") as fh:
        json.dump({**DOTS3, "swa_attention_gate_type": "elementwise"}, fh)
    with pytest.raises(NotImplementedError, match="swa_attention_gate_type"):
        config_from_hf(str(tmp_path))
