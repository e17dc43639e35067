"""Inference v2 (FastGen) tests.

Reference pattern: tests/unit/inference/v2/ — ragged components tested
standalone, plus end-to-end continuous-batching correctness: interleaved
scheduling must produce the SAME tokens as sequential generation.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.inference.v2 import (InferenceEngineV2,
                                        RaggedInferenceEngineConfig)
from deepspeed_tpu.inference.v2.model_implementations import RaggedLlama
from deepspeed_tpu.inference.v2.ragged import (BlockedAllocator,
                                               RaggedBatchWrapper)
from deepspeed_tpu.inference.v2.ragged.sequence_descriptor import (
    DSSequenceDescriptor,
)
from deepspeed_tpu.models import LlamaConfig, LlamaForCausalLM
from deepspeed_tpu.parallel import groups

CFG = LlamaConfig.tiny(dtype=jnp.float32)


def _params():
    model = LlamaForCausalLM(CFG)
    return model.init(jax.random.key(0),
                      np.zeros((1, 4), np.int32))["params"]


def _v2_engine(params, token_budget=16, block_size=8, max_context=64,
               max_seqs=4):
    cfg = RaggedInferenceEngineConfig.from_dict({
        "state_manager": {"max_ragged_batch_size": token_budget,
                          "max_ragged_sequence_count": max_seqs,
                          "max_context": max_context},
        "kv_cache": {"block_size": block_size},
    })
    return InferenceEngineV2(RaggedLlama(CFG, block_size), params, cfg)


# --------------------------------------------------------------------- #
# Ragged components standalone
# --------------------------------------------------------------------- #
def test_blocked_allocator():
    a = BlockedAllocator(8)
    assert a.free_blocks == 7  # block 0 is the trash block
    got = a.allocate(3)
    assert len(got) == 3 and 0 not in got
    a.free(got)
    assert a.free_blocks == 7
    with pytest.raises(RuntimeError):
        a.allocate(8)
    with pytest.raises(ValueError):
        a.free([0])


def test_ragged_wrapper_metadata():
    w = RaggedBatchWrapper(token_budget=16, max_seqs=4, max_blocks=4,
                           block_size=4)
    s1 = DSSequenceDescriptor(uid=1, seen_tokens=0, blocks=[2])
    s2 = DSSequenceDescriptor(uid=2, seen_tokens=5, blocks=[3, 1])
    w.insert_sequence(s1, np.asarray([7, 8, 9], np.int32))
    w.insert_sequence(s2, np.asarray([4], np.int32))
    m = w.finalize()
    np.testing.assert_array_equal(m["token_ids"][:4], [7, 8, 9, 4])
    np.testing.assert_array_equal(m["token_slot"][:4], [0, 0, 0, 1])
    np.testing.assert_array_equal(m["token_pos"][:4], [0, 1, 2, 5])
    # kv_dest: s1 pos 0..2 in block 2 -> 8,9,10; s2 pos 5 -> block idx 1
    # (block id 1), offset 1 -> 1*4+1 = 5
    np.testing.assert_array_equal(m["kv_dest"][:4], [8, 9, 10, 5])
    assert m["logits_idx"][0] == 2 and m["logits_idx"][1] == 3
    np.testing.assert_array_equal(m["context_lens"][:2], [3, 6])
    # pads scatter to the trash block
    assert (m["kv_dest"][4:] == 0).all()


def test_state_manager_alloc_flush():
    params = _params()
    eng = _v2_engine(params, block_size=4, max_context=16)
    sm = eng.state_manager
    free0 = sm.free_blocks
    seq = sm.get_or_create_sequence(1)
    sm.maybe_allocate_kv(seq, 6)          # 6 tokens / bs=4 -> 2 blocks
    assert len(seq.blocks) == 2 and sm.free_blocks == free0 - 2
    sm.maybe_allocate_kv(seq, 6)          # still within 2 blocks? 6 > 8? no
    seq.seen_tokens = 6
    sm.maybe_allocate_kv(seq, 4)          # 10 total -> 3 blocks
    assert len(seq.blocks) == 3
    sm.flush_sequence(1)
    assert sm.free_blocks == free0
    with pytest.raises(ValueError):
        sm.flush_sequence(1)


# --------------------------------------------------------------------- #
# End-to-end correctness
# --------------------------------------------------------------------- #
def _v1_reference_tokens(params, prompts, n_new):
    """Greedy tokens from the v1 engine, one prompt at a time."""
    topo = groups.initialize_mesh(model_parallel_size=1)
    eng = deepspeed_tpu.init_inference(
        model=LlamaForCausalLM(CFG), config={"dtype": "fp32"},
        topology=topo)
    eng.params = jax.device_put(params)
    outs = []
    for p in prompts:
        full = np.asarray(eng.generate(np.asarray(p, np.int32)[None],
                                       max_new_tokens=n_new))
        outs.append(full[0, len(p):])
    return outs


def test_continuous_batching_matches_sequential():
    """Interleaved ragged scheduling == one-at-a-time v1 generation."""
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, CFG.vocab_size, size=(n,)).tolist()
               for n in (5, 11, 3)]
    params = _params()
    ref = _v1_reference_tokens(params, prompts, n_new=8)

    eng = _v2_engine(params, token_budget=8, block_size=8, max_context=64)
    # budget 8 < prompt lengths sum -> SplitFuse chunking is exercised
    out = eng.generate(prompts, max_new_tokens=8)
    for got, want in zip(out, ref):
        np.testing.assert_array_equal(got, np.asarray(want))


def test_staggered_arrival_matches_sequential():
    """A sequence that joins mid-stream doesn't perturb others."""
    rng = np.random.default_rng(4)
    p1 = rng.integers(0, CFG.vocab_size, size=(6,)).tolist()
    p2 = rng.integers(0, CFG.vocab_size, size=(4,)).tolist()
    params = _params()
    ref1, ref2 = _v1_reference_tokens(params, [p1, p2], n_new=6)

    eng = _v2_engine(params, token_budget=16, block_size=8)
    got1 = []
    logits = eng.put([1], [p1])
    tok1 = int(np.argmax(logits[1]))
    got1.append(tok1)
    # two decode steps for seq 1 alone
    for _ in range(2):
        logits = eng.put([1], [[tok1]])
        tok1 = int(np.argmax(logits[1]))
        got1.append(tok1)
    # seq 2 arrives; both decode together in the same ragged batches
    logits = eng.put([1, 2], [[tok1], p2])
    tok1 = int(np.argmax(logits[1]))
    tok2 = int(np.argmax(logits[2]))
    got1.append(tok1)
    got2 = [tok2]
    for _ in range(5):
        logits = eng.put([1, 2], [[tok1], [tok2]])
        tok1, tok2 = int(np.argmax(logits[1])), int(np.argmax(logits[2]))
        got1.append(tok1)
        got2.append(tok2)
    eng.flush([1, 2])
    np.testing.assert_array_equal(got1[:6], ref1)
    np.testing.assert_array_equal(got2, ref2)


def test_kv_blocks_freed_after_flush():
    params = _params()
    eng = _v2_engine(params)
    free0 = eng.state_manager.free_blocks
    eng.generate([[1, 2, 3], [4, 5]], max_new_tokens=4)
    assert eng.state_manager.free_blocks == free0
    assert eng.state_manager.n_tracked_sequences == 0


def test_can_schedule_budget_and_blocks():
    params = _params()
    eng = _v2_engine(params, token_budget=8, max_seqs=2, block_size=8,
                     max_context=16)
    assert eng.can_schedule([1], [8])
    assert not eng.can_schedule([1], [9])            # token budget
    assert not eng.can_schedule([1, 2, 3], [1, 1, 1])  # seq slots
    # exhaust KV blocks: cache has ceil(16/8)*2+1 = 5 blocks, 4 usable
    assert not eng.can_schedule([1, 2], [8 * 4, 8])


def test_max_context_enforced():
    params = _params()
    eng = _v2_engine(params, token_budget=8, block_size=8, max_context=16)
    assert not eng.can_schedule([1], [17])
    with pytest.raises(RuntimeError, match="max_context"):
        eng.put([1], [list(range(17))])
    eng.put([1], [[1, 2, 3]])
    assert eng.query(1)["max_new_tokens"] == 13
    with pytest.raises(ValueError, match="empty"):
        eng.put([1], [[]])
    eng.flush([1])


def test_query_reports_state():
    params = _params()
    eng = _v2_engine(params)
    assert eng.query(9)["tracked"] is False
    eng.put([9], [[1, 2, 3]])
    q = eng.query(9)
    assert q["tracked"] and q["seen_tokens"] == 3 and q["pending_tokens"] == 0
    eng.flush([9])


# ------------------------------------------------------------------ #
# blocked-flash paged attention kernel (reference
# inference/v2/kernels/ragged_ops/blocked_flash/)
# ------------------------------------------------------------------ #
def test_paged_attention_kernel_matches_xla_reference():
    import jax
    import jax.numpy as jnp
    import numpy as np

    from deepspeed_tpu.inference.v2.kernels import paged_attention
    from deepspeed_tpu.inference.v2.modules.attention import (
        _paged_attention)

    rng = np.random.default_rng(7)
    bs, nb, hkv, d, h = 8, 8, 2, 16, 8  # GQA group 4
    k_pool = jnp.asarray(rng.normal(size=(nb * bs, hkv, d)).astype(
        np.float32))
    v_pool = jnp.asarray(rng.normal(size=(nb * bs, hkv, d)).astype(
        np.float32))
    tables = jnp.asarray([[0, 1, 2, 5], [3, 4, 0, 0]], jnp.int32)
    token_slot = jnp.asarray([0, 1, 0, 1, 0], jnp.int32)
    token_pos = jnp.asarray([25, 14, 7, 0, 31], jnp.int32)
    q = jnp.asarray(rng.normal(size=(5, h, d)).astype(np.float32))

    batch = {"block_tables": tables, "token_slot": token_slot,
             "token_pos": token_pos}
    ref = _paged_attention(q, k_pool, v_pool, batch, bs, use_kernel=False)
    got = paged_attention(q, k_pool, v_pool, tables, token_slot, token_pos,
                          block_size=bs)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_ragged_engine_with_kernel_path():
    """Full put/query/flush engine run with the Pallas kernel forced on
    (interpret mode on CPU): outputs must match the XLA-path engine."""
    import numpy as np

    import deepspeed_tpu.inference.v2.modules.attention as rl

    orig = rl._paged_attention

    def forced(q, k_pool, v_pool, batch, block_size, use_kernel=None,
               **kw):
        kw.pop("decode_mode", None)
        return orig(q, k_pool, v_pool, batch, block_size, use_kernel=True,
                    **kw)

    params = _params()
    engine_ref = _v2_engine(params)
    ids = np.random.default_rng(3).integers(
        0, CFG.vocab_size, size=(12,)).astype(np.int32)
    ref_logits = engine_ref.put([7], [ids])

    rl._paged_attention = forced
    try:
        engine_k = _v2_engine(params)
        k_logits = engine_k.put([7], [ids])
    finally:
        rl._paged_attention = orig
    np.testing.assert_allclose(np.asarray(k_logits[7]),
                               np.asarray(ref_logits[7]),
                               rtol=2e-4, atol=2e-4)


# ------------------------------------------------------------------ #
# Tensor parallelism (reference inference/v2/model_implementations/
# sharding/{qkv,attn_out,mlp,embedding,unembed}.py)
# ------------------------------------------------------------------ #
TP_CFG = LlamaConfig.tiny(num_key_value_heads=4, dtype=jnp.float32)


def _tp_params():
    return LlamaForCausalLM(TP_CFG).init(
        jax.random.key(0), np.zeros((1, 4), np.int32))["params"]


def _tp_engine(params, tp, token_budget=16, block_size=8, max_context=64):
    topo = groups.initialize_mesh(model_parallel_size=tp)
    cfg = RaggedInferenceEngineConfig.from_dict({
        "state_manager": {"max_ragged_batch_size": token_budget,
                          "max_ragged_sequence_count": 4,
                          "max_context": max_context},
        "kv_cache": {"block_size": block_size},
    })
    model = RaggedLlama(TP_CFG, block_size, mesh=topo.mesh)
    return InferenceEngineV2(model, params, cfg)


@pytest.mark.parametrize("tp", [2, 4])
def test_v2_tensor_parallel_matches_tp1(tp):
    """put/query/flush token parity at model=2 and model=4: the shard_map
    TP forward must generate exactly the tp=1 engine's tokens."""
    rng = np.random.default_rng(11)
    prompts = [rng.integers(0, TP_CFG.vocab_size, size=(n,)).tolist()
               for n in (7, 3)]
    params = _tp_params()
    groups.initialize_mesh(model_parallel_size=1)
    eng1 = InferenceEngineV2(
        RaggedLlama(TP_CFG, 8), params,
        RaggedInferenceEngineConfig.from_dict({
            "state_manager": {"max_ragged_batch_size": 16,
                              "max_ragged_sequence_count": 4,
                              "max_context": 64},
            "kv_cache": {"block_size": 8}}))
    want = eng1.generate(prompts, max_new_tokens=6)

    eng = _tp_engine(params, tp)
    got = eng.generate(prompts, max_new_tokens=6)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g, w)


def test_v2_tp_rejects_indivisible_heads():
    topo = groups.initialize_mesh(model_parallel_size=8)
    with pytest.raises(ValueError, match="divisible"):
        RaggedLlama(LlamaConfig.tiny(num_key_value_heads=2), 8,
                    mesh=topo.mesh)  # hkv=2 % 8 != 0


def test_v2_tp_hlo_only_rowparallel_allreduce():
    """The TP step's HLO carries exactly the Megatron collective pattern:
    one psum for the vocab-split embedding + 2 per layer (attn-out,
    mlp-down), and one all-gather for the vocab-split unembed — nothing
    else (no per-projection resharding)."""
    from deepspeed_tpu.inference.v2.modules.attention import (
        kv_spec, shard_ragged_params)
    from deepspeed_tpu.inference.v2.ragged.kv_cache import BlockedKVCache
    from jax.sharding import NamedSharding

    params = _tp_params()
    topo = groups.initialize_mesh(model_parallel_size=2)
    model = RaggedLlama(TP_CFG, 8, mesh=topo.mesh)
    params = shard_ragged_params(params, topo.mesh)
    # the pool as the engine places it: the stored row, its KV heads split
    cache = jax.tree.map(
        lambda x: jax.device_put(x, NamedSharding(topo.mesh, kv_spec(x))),
        BlockedKVCache(TP_CFG.num_hidden_layers, 4, 8,
                       TP_CFG.num_key_value_heads, TP_CFG.head_dim,
                       jnp.float32).cache)
    meta = {
        "token_ids": jnp.zeros((8,), jnp.int32),
        "token_slot": jnp.zeros((8,), jnp.int32),
        "token_pos": jnp.arange(8, dtype=jnp.int32),
        "kv_dest": jnp.arange(8, dtype=jnp.int32),
        "block_tables": jnp.zeros((4, 4), jnp.int32),
        "context_lens": jnp.zeros((4,), jnp.int32),
        "logits_idx": jnp.zeros((4,), jnp.int32),
    }
    txt = jax.jit(model.__call__).lower(params, cache, meta).as_text()
    n_ar = txt.count("stablehlo.all_reduce")
    n_ag = txt.count("stablehlo.all_gather\"")
    want_ar = 1 + 2 * TP_CFG.num_hidden_layers
    assert n_ar == want_ar, f"expected {want_ar} all-reduces, HLO has {n_ar}"
    assert n_ag == 1, f"expected 1 all-gather (unembed), HLO has {n_ag}"


# ------------------------------------------------------------------ #
# Mistral sliding-window serving (reference inference/v2/
# model_implementations/mistral/ + SWA in the blocked-flash kernel)
# ------------------------------------------------------------------ #
def test_paged_attention_kernel_window_matches_xla():
    from deepspeed_tpu.inference.v2.kernels import paged_attention
    from deepspeed_tpu.inference.v2.modules.attention import (
        _paged_attention)

    rng = np.random.default_rng(9)
    bs, nb, hkv, d, h, W = 8, 8, 2, 16, 4, 12
    k_pool = jnp.asarray(rng.normal(size=(nb * bs, hkv, d)).astype(np.float32))
    v_pool = jnp.asarray(rng.normal(size=(nb * bs, hkv, d)).astype(np.float32))
    tables = jnp.asarray([[1, 2, 3, 4], [5, 6, 0, 0]], jnp.int32)
    token_slot = jnp.asarray([0, 0, 1, 0], jnp.int32)
    token_pos = jnp.asarray([30, 13, 11, 5], jnp.int32)  # 30 crosses window
    q = jnp.asarray(rng.normal(size=(4, h, d)).astype(np.float32))
    batch = {"block_tables": tables, "token_slot": token_slot,
             "token_pos": token_pos}
    ref = _paged_attention(q, k_pool, v_pool, batch, bs, use_kernel=False,
                           window=W)
    got = paged_attention(q, k_pool, v_pool, tables, token_slot, token_pos,
                          block_size=bs, window=W)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_v2_mistral_swa_matches_v1_past_window():
    """Ragged Mistral (SWA) == v1 engine token-for-token, with generation
    running PAST the window boundary (context 10+24 > window 16)."""
    from deepspeed_tpu.models.mistral import mistral_tiny

    cfg = mistral_tiny(dtype=jnp.float32)        # sliding_window=16
    params = LlamaForCausalLM(cfg).init(
        jax.random.key(1), np.zeros((1, 4), np.int32))["params"]
    rng = np.random.default_rng(5)
    prompt = rng.integers(0, cfg.vocab_size, size=(10,)).tolist()

    topo = groups.initialize_mesh(model_parallel_size=1)
    v1 = deepspeed_tpu.init_inference(model=LlamaForCausalLM(cfg),
                                      config={"dtype": "fp32"},
                                      topology=topo)
    v1.params = jax.device_put(params)
    want = np.asarray(v1.generate(np.asarray(prompt, np.int32)[None],
                                  max_new_tokens=24))[0, len(prompt):]

    eng = InferenceEngineV2(
        RaggedLlama(cfg, 8), params,
        RaggedInferenceEngineConfig.from_dict({
            "state_manager": {"max_ragged_batch_size": 16,
                              "max_ragged_sequence_count": 4,
                              "max_context": 64},
            "kv_cache": {"block_size": 8}}))
    got = eng.generate([prompt], max_new_tokens=24)[0]
    assert len(prompt) + len(got) > cfg.sliding_window
    np.testing.assert_array_equal(got, want)


# ------------------------------------------------------------------ #
# Mixtral MoE serving (reference inference/v2/model_implementations/
# mixtral/ + ragged_ops/{top_k_gating,moe_scatter,moe_gather})
# ------------------------------------------------------------------ #
def test_v2_mixtral_matches_cache_free_forward():
    from deepspeed_tpu.inference.v2.model_implementations import RaggedMixtral
    from deepspeed_tpu.models.mixtral import MixtralConfig, MixtralForCausalLM

    # ample capacity -> the training forward's capacity gating == dropless
    cfg = MixtralConfig.tiny(dtype=jnp.float32, moe_capacity_factor=8.0)
    model = MixtralForCausalLM(cfg)
    params = model.init(jax.random.key(2),
                        np.zeros((1, 4), np.int32))["params"]
    rng = np.random.default_rng(6)
    prompts = [rng.integers(0, cfg.vocab_size, size=(n,)).tolist()
               for n in (6, 3)]

    # cache-free greedy reference: full forward per emitted token
    def ref_tokens(prompt, n_new):
        ids = list(prompt)
        out = []
        for _ in range(n_new):
            logits = model.apply({"params": params},
                                 np.asarray(ids, np.int32)[None],
                                 train=False)
            nxt = int(np.argmax(np.asarray(logits)[0, -1]))
            out.append(nxt)
            ids.append(nxt)
        return out

    want = [ref_tokens(p, 6) for p in prompts]

    groups.initialize_mesh(model_parallel_size=1)
    eng = InferenceEngineV2(
        RaggedMixtral(cfg, 8), params,
        RaggedInferenceEngineConfig.from_dict({
            "state_manager": {"max_ragged_batch_size": 8,
                              "max_ragged_sequence_count": 4,
                              "max_context": 64},
            "kv_cache": {"block_size": 8}}))
    got = eng.generate(prompts, max_new_tokens=6)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g, np.asarray(w))


def test_generate_more_prompts_than_max_seqs():
    """generate() with more prompts than sequence slots chunks across
    groups on the device-resident decode path too."""
    params = _params()
    eng = _v2_engine(params, token_budget=16, block_size=8, max_seqs=2)
    rng = np.random.default_rng(8)
    prompts = [rng.integers(0, CFG.vocab_size, size=(4,)).tolist()
               for _ in range(3)]
    ref = _v1_reference_tokens(params, prompts, n_new=5)
    out = eng.generate(prompts, max_new_tokens=5)
    for got, want in zip(out, ref):
        np.testing.assert_array_equal(got, np.asarray(want))


def test_decode_loop_validates_lengths():
    params = _params()
    eng = _v2_engine(params)
    eng.put([1, 2], [[3, 4], [5]])
    with pytest.raises(ValueError, match="tokens"):
        eng.decode_loop([1, 2], [7], steps=2)
    eng.flush([1, 2])


def test_decode_loop_chunking_matches_put_loop():
    """steps=7 decomposes into 4+1+1+1 chunks; tokens must equal the
    per-put() decode loop."""
    params = _params()
    rng = np.random.default_rng(12)
    prompt = rng.integers(0, CFG.vocab_size, size=(6,)).tolist()

    eng1 = _v2_engine(params)
    logits = eng1.put([1], [prompt])
    t = int(np.argmax(logits[1]))
    want = [t]
    for _ in range(7):
        logits = eng1.put([1], [[t]])
        t = int(np.argmax(logits[1]))
        want.append(t)
    eng1.flush([1])

    eng2 = _v2_engine(params)
    logits = eng2.put([1], [prompt])
    t0 = int(np.argmax(logits[1]))
    toks = eng2.decode_loop([1], [t0], steps=7)
    eng2.flush([1])
    np.testing.assert_array_equal([t0] + toks[0].tolist(), want)


def test_decode_step_large_pool_matches_put_loop():
    """A KV pool much larger than the live contexts (num_blocks set high)
    must route decode to the bounded gather path, not the dense-pool
    program, and still match the per-put() loop."""
    params = _params()
    cfg = RaggedInferenceEngineConfig.from_dict({
        "state_manager": {"max_ragged_batch_size": 16,
                          "max_ragged_sequence_count": 2,
                          "max_context": 32},
        "kv_cache": {"block_size": 8, "num_blocks": 64},
    })
    eng = InferenceEngineV2(RaggedLlama(CFG, 8), params, cfg)
    # pool rows (64*8=512) > 2 * S * C (2 * 2 * 4*8 = 128): gather path
    assert 64 * 8 > 2 * 2 * (32 // 8) * 8
    prompt = np.random.default_rng(21).integers(
        0, CFG.vocab_size, size=(6,)).tolist()
    logits = eng.put([1], [prompt])
    t = int(np.argmax(logits[1]))
    want = []
    for _ in range(5):
        logits = eng.put([1], [[t]])
        t = int(np.argmax(logits[1]))
        want.append(t)
    eng.flush([1])

    eng2 = InferenceEngineV2(RaggedLlama(CFG, 8), params, cfg)
    logits = eng2.put([1], [prompt])
    nxt = [int(np.argmax(logits[1]))]
    got = []
    for _ in range(5):
        _lg, nxt = eng2.decode_step([1], nxt, greedy=True)
        got.append(int(np.asarray(nxt)[0]))
    eng2.flush([1])
    np.testing.assert_array_equal(got, want)


def test_decode_step_matches_put_loop():
    """decode_step (device-resident token feedback, one dispatch per
    token) must produce the same greedy tokens as the per-put() loop,
    including across a block-table growth boundary (block_size=8 with a
    6-token prompt crosses into a new block at step 2) and across an
    interleaved put() that invalidates the device-resident metadata."""
    import jax.numpy as jnp

    params = _params()
    rng = np.random.default_rng(13)
    prompts = [rng.integers(0, CFG.vocab_size, size=(6,)).tolist(),
               rng.integers(0, CFG.vocab_size, size=(4,)).tolist()]

    eng1 = _v2_engine(params)
    logits = eng1.put([1, 2], prompts)
    cur = {u: int(np.argmax(logits[u])) for u in (1, 2)}
    want = {1: [cur[1]], 2: [cur[2]]}
    for _ in range(10):
        logits = eng1.put([1, 2], [[cur[1]], [cur[2]]])
        cur = {u: int(np.argmax(logits[u])) for u in (1, 2)}
        want[1].append(cur[1])
        want[2].append(cur[2])
    eng1.flush([1, 2])

    eng2 = _v2_engine(params)
    logits = eng2.put([1, 2], prompts)
    got = {1: [], 2: []}
    tok = [int(np.argmax(logits[1])), int(np.argmax(logits[2]))]
    got[1].append(tok[0])
    got[2].append(tok[1])
    nxt = tok
    for step in range(10):
        lg, nxt = eng2.decode_step([1, 2], nxt, greedy=True)
        host = np.asarray(nxt)[:2]
        # greedy argmax inside the program == argmax of returned logits
        np.testing.assert_array_equal(
            host, np.argmax(np.asarray(lg[:2], np.float32), axis=-1))
        got[1].append(int(host[0]))
        got[2].append(int(host[1]))
        if step == 4:
            # interleaved scheduling activity forces a metadata
            # re-upload on the next decode_step
            eng2.put([9], [[5, 6, 7]])
            eng2.flush([9])
            nxt = jnp.asarray(host)
    eng2.flush([1, 2])
    np.testing.assert_array_equal(got[1], want[1])
    np.testing.assert_array_equal(got[2], want[2])


# ------------------------------------------------------------------ #
# Debug-mode ragged invariants: corrupt metadata must raise, not return
# wrong logits (the paged kernel masks by position only)
# ------------------------------------------------------------------ #
def test_ragged_debug_catches_shared_block():
    from deepspeed_tpu.inference.v2.ragged.ragged_wrapper import (
        RaggedMetadataError)

    params = _params()
    eng = _v2_engine(params, block_size=8)
    eng.put([1, 2], [[1, 2, 3], [4, 5]])
    s1 = eng.state_manager.get_sequence(1)
    s2 = eng.state_manager.get_sequence(2)
    s2.blocks[0] = s1.blocks[0]  # corrupt: share a KV block
    with pytest.raises(RaggedMetadataError, match="owned by both"):
        eng.put([1, 2], [[7], [8]])


def test_ragged_debug_catches_capacity_overrun():
    from deepspeed_tpu.inference.v2.ragged.ragged_wrapper import (
        RaggedMetadataError, validate_ragged_metadata)

    # 7 seen + 2 new = 9 positions, one 8-wide block: the write for
    # position 8 would land in another sequence's block
    seq = DSSequenceDescriptor(uid=1, seen_tokens=7, blocks=[3])
    with pytest.raises(RaggedMetadataError, match="spill"):
        validate_ragged_metadata([seq], [np.zeros(2, np.int32)], 8)
    seq.seen_tokens = -1
    with pytest.raises(RaggedMetadataError, match="negative"):
        validate_ragged_metadata([seq], [np.zeros(1, np.int32)], 8)


def test_ragged_debug_catches_trash_ownership():
    from deepspeed_tpu.inference.v2.ragged.ragged_wrapper import (
        RaggedMetadataError)

    params = _params()
    eng = _v2_engine(params, block_size=8)
    eng.put([1], [[1, 2, 3]])
    seq = eng.state_manager.get_sequence(1)
    seq.blocks[0] = 0  # corrupt: the trash block
    with pytest.raises(RaggedMetadataError, match="trash"):
        eng.put([1], [[7]])


def test_ragged_debug_guards_decode_loop():
    from deepspeed_tpu.inference.v2.ragged.ragged_wrapper import (
        RaggedMetadataError)

    params = _params()
    eng = _v2_engine(params, block_size=8)
    logits = eng.put([1, 2], [[1, 2, 3], [4, 5]])
    s1 = eng.state_manager.get_sequence(1)
    s2 = eng.state_manager.get_sequence(2)
    s2.blocks[0] = s1.blocks[0]
    with pytest.raises(RaggedMetadataError, match="owned by both"):
        eng.decode_loop([1, 2],
                        [int(np.argmax(logits[1])),
                         int(np.argmax(logits[2]))], steps=2)


# ------------------------------------------------------------------ #
# serialize (reference engine_v2.py:237 + flat_model_helpers.py)
# ------------------------------------------------------------------ #
def test_v2_serialize_roundtrip(tmp_path):
    params = _params()
    eng = _v2_engine(params)
    rng = np.random.default_rng(13)
    prompt = rng.integers(0, CFG.vocab_size, size=(6,)).tolist()
    want = eng.generate([prompt], max_new_tokens=5)[0]

    eng.serialize(str(tmp_path / "ckpt"))
    assert (tmp_path / "ckpt" / "model.bin").exists()
    assert (tmp_path / "ckpt" / "metadata.json").exists()

    eng2 = InferenceEngineV2.load_serialized(
        str(tmp_path / "ckpt"), RaggedLlama(CFG, 8),
        RaggedInferenceEngineConfig.from_dict({
            "state_manager": {"max_ragged_batch_size": 16,
                              "max_ragged_sequence_count": 4,
                              "max_context": 64},
            "kv_cache": {"block_size": 8}}))
    got = eng2.generate([prompt], max_new_tokens=5)[0]
    np.testing.assert_array_equal(got, want)


# ------------------------------------------------------------------ #
# Tiled prefill (reference ragged_ops/atom_builder work units)
# ------------------------------------------------------------------ #
def test_tiled_prefill_kernel_matches_xla():
    from deepspeed_tpu.inference.v2.kernels import paged_prefill_attention
    from deepspeed_tpu.inference.v2.modules.attention import (
        _paged_attention)

    rng = np.random.default_rng(15)
    bs, nb, hkv, d, h, tile = 8, 12, 2, 16, 4, 16
    k_pool = jnp.asarray(rng.normal(size=(nb * bs, hkv, d)).astype(np.float32))
    v_pool = jnp.asarray(rng.normal(size=(nb * bs, hkv, d)).astype(np.float32))
    tables = jnp.asarray([[1, 2, 3, 4], [5, 6, 0, 0]], jnp.int32)
    # two tile-aligned chunks: seq0 rows 0..21 (pos 10..31), pads 22..31;
    # seq1 rows 32..40 (pos 0..8), pads 41..47
    T = 48
    token_slot = np.zeros((T,), np.int32)
    token_pos = np.full((T,), -1, np.int32)
    token_slot[0:22] = 0
    token_pos[0:22] = np.arange(10, 32)
    token_slot[32:41] = 1
    token_pos[32:41] = np.arange(0, 9)
    q = jnp.asarray(rng.normal(size=(T, h, d)).astype(np.float32))
    batch = {"block_tables": tables,
             "token_slot": jnp.asarray(token_slot),
             "token_pos": jnp.asarray(token_pos)}
    ref = _paged_attention(q, k_pool, v_pool, batch, bs, use_kernel=False)
    got = paged_prefill_attention(
        q, k_pool, v_pool, tables, jnp.asarray(token_slot),
        jnp.asarray(token_pos), block_size=bs, tile_q=tile)
    real = np.r_[0:22, 32:41]
    np.testing.assert_allclose(np.asarray(got)[real], np.asarray(ref)[real],
                               rtol=2e-5, atol=2e-5)
    # pad rows are exact zeros (not NaN)
    pads = np.r_[22:32, 41:48]
    assert np.all(np.asarray(got)[pads] == 0)


def test_tiled_prefill_kernel_window_matches_xla():
    from deepspeed_tpu.inference.v2.kernels import paged_prefill_attention
    from deepspeed_tpu.inference.v2.modules.attention import (
        _paged_attention)

    rng = np.random.default_rng(16)
    bs, nb, hkv, d, h, tile, W = 8, 12, 2, 16, 4, 16, 12
    k_pool = jnp.asarray(rng.normal(size=(nb * bs, hkv, d)).astype(np.float32))
    v_pool = jnp.asarray(rng.normal(size=(nb * bs, hkv, d)).astype(np.float32))
    tables = jnp.asarray([[1, 2, 3, 4]], jnp.int32)
    T = 32
    token_slot = np.zeros((T,), np.int32)
    token_pos = np.full((T,), -1, np.int32)
    token_pos[0:30] = np.arange(0, 30)
    q = jnp.asarray(rng.normal(size=(T, h, d)).astype(np.float32))
    batch = {"block_tables": tables,
             "token_slot": jnp.asarray(token_slot),
             "token_pos": jnp.asarray(token_pos)}
    ref = _paged_attention(q, k_pool, v_pool, batch, bs, use_kernel=False,
                           window=W)
    got = paged_prefill_attention(
        q, k_pool, v_pool, tables, jnp.asarray(token_slot),
        jnp.asarray(token_pos), block_size=bs, tile_q=tile, window=W)
    np.testing.assert_allclose(np.asarray(got)[:30], np.asarray(ref)[:30],
                               rtol=2e-5, atol=2e-5)


# the edges of the tiled kernel's grid: key steps of four table entries
# (block 128: the rule's own choice) counted from a tile's first live block.
# name: (heads, KV heads, head size, table entries, window, tiles), a tile
# (slot, first position, tokens) or None, a tile of pads
_TILED_EDGES = {
    # the band starts in the third entry of a step and ends in the third of
    # the next; the last tile is short
    "band_mid_step": (4, 2, 16, 16, 700,
                      [(0, 1000, 128), (0, 1128, 128), (0, 1256, 44)]),
    # positions under the window (every key visible) beside a tile far past it
    "ramp_and_far": (4, 2, 16, 16, 300,
                     [(0, 0, 128), (0, 128, 128), (1, 1500, 128)]),
    # a step wholly inside the band (no mask) between two on its edges
    "window_inside": (4, 2, 16, 16, 1100, [(0, 1400, 128)]),
    "short_table": (4, 2, 16, 3, None, [(0, 100, 128), (0, 228, 72)]),
    "short_table_window": (4, 2, 16, 3, 150, [(0, 100, 128), (0, 228, 72)]),
    # fifteen live blocks beside one: three of the second tile's four steps
    # do nothing
    "two_lengths": (4, 2, 16, 16, None, [(0, 1800, 128), (1, 0, 40)]),
    "pad_tile_between": (4, 2, 16, 8, None,
                         [(0, 300, 128), None, (1, 0, 100)]),
    "pad_tile_between_window": (4, 2, 16, 8, 200,
                                [(0, 300, 128), None, (1, 600, 100)]),
    "group_1": (2, 2, 16, 8, None, [(0, 200, 128), (0, 328, 128)]),
    # two KV heads a lane tile, two tiles a row: the loop over lane tiles
    "group_4_d64": (16, 4, 64, 8, 400, [(0, 500, 128), (0, 628, 128)]),
    "group_1_d64": (4, 4, 64, 8, None, [(1, 0, 128), (0, 640, 128)]),
    "group_6": (12, 2, 16, 8, None, [(0, 130, 128), (1, 0, 7)]),
    # a KV head a lane tile: the loop over KV heads, six query heads each
    "group_6_d128": (12, 2, 128, 8, 300, [(0, 600, 128)]),
}


@pytest.mark.parametrize("name", list(_TILED_EDGES))
def test_tiled_prefill_kernel_edges_match_xla(name):
    from deepspeed_tpu.inference.v2.kernels import (blocked_flash,
                                                    paged_prefill_attention)
    from deepspeed_tpu.inference.v2.modules.attention import (
        _paged_attention)

    h, hkv, d, entries, window, tiles = _TILED_EDGES[name]
    bs = tile = 128
    assert blocked_flash._prefill_step_blocks(
        h // hkv, bs, entries, window, tile) == min(4, entries)
    rng = np.random.default_rng(44)
    nb = 2 * entries + 1
    pool = lambda: jnp.asarray(
        rng.normal(size=(nb * bs, hkv, d)).astype(np.float32))
    k_pool, v_pool = pool(), pool()
    # two sequences' blocks interleaved in the pool, block 0 the trash block
    tables = jnp.asarray(
        1 + rng.permutation(nb - 1).reshape(2, entries), jnp.int32)
    slot = np.zeros((len(tiles) * tile,), np.int32)
    pos = np.full((len(tiles) * tile,), -1, np.int32)
    for i, chunk in enumerate(tiles):
        if chunk is not None:
            s, start, n = chunk
            slot[i * tile:(i + 1) * tile] = s
            pos[i * tile:i * tile + n] = np.arange(start, start + n)
    q = jnp.asarray(rng.normal(size=(len(pos), h, d)).astype(np.float32))
    batch = {"block_tables": tables, "token_slot": jnp.asarray(slot),
             "token_pos": jnp.asarray(pos)}
    ref = _paged_attention(q, k_pool, v_pool, batch, bs, use_kernel=False,
                           window=window)
    got = paged_prefill_attention(
        q, k_pool, v_pool, tables, jnp.asarray(slot), jnp.asarray(pos),
        block_size=bs, tile_q=tile, window=window)
    np.testing.assert_allclose(np.asarray(got)[pos >= 0],
                               np.asarray(ref)[pos >= 0],
                               rtol=2e-5, atol=2e-5)
    assert np.all(np.asarray(got)[pos < 0] == 0)  # pads: exact zeros
    # the host's count of the grid: no step outside the table or the band,
    # every tile with a row holds a live step, a tile of pads none
    chunks = [(start, n) for c in tiles if c for _, start, n in [c]]
    steps, live = blocked_flash.prefill_key_steps(
        chunks, len(tiles), group=h // hkv, block_size=bs, entries=entries,
        window=window, tile_q=tile)
    assert len(chunks) <= live <= steps <= len(tiles) * -(-entries // 4)


# cell: (group, table entries, window) -> (entries a step, key steps a tile,
# live steps of a 1,024-token chunk from position 6,144, or from the last
# position the table holds one)
@pytest.mark.parametrize("cell, shape, want", [
    ("trinity_window", (6, 200, 4096), (4, 9, 8 * 9)),
    ("trinity_global", (6, 200, None), (4, 50, sum(
        (6144 + 128 * i + 127) // 128 // 4 + 1 for i in range(8)))),
    ("mistral7b", (4, 32, 4096), (4, 8, sum(
        (3072 + 128 * i + 127) // 128 // 4 + 1 for i in range(8)))),
    ("lfm2", (4, 70, None), (4, 18, None)),
    ("qwen3next", (8, 36, None), (4, 9, None)),
    ("olmoe", (1, 32, None), (4, 8, None)),
    ("ouro", (1, 8, None), (4, 2, 4 * 1 + 4 * 2)),
])
def test_tiled_prefill_grid_at_the_cells_shapes(cell, shape, want):
    from deepspeed_tpu.inference.v2.kernels import blocked_flash

    group, entries, window = shape
    kb, steps, live = want
    assert blocked_flash._prefill_step_blocks(
        group, 128, entries, window, 128) == kb
    assert blocked_flash._prefill_key_steps(
        entries, window, 128, 128, kb) == steps
    start = min(6144, entries * 128 - 1024)
    got = blocked_flash.prefill_key_steps(
        [(start, 1024)], 8, group=group, block_size=128, entries=entries,
        window=window, tile_q=128)
    assert got[0] == 8 * steps and got[1] <= got[0]
    if live is not None:
        assert got[1] == live


@pytest.mark.parametrize("cell, h, hkv, d, entries, window", [
    ("trinity_window", 48, 8, 128, 200, 4096),
    ("trinity_global", 48, 8, 128, 200, None),
    ("mistral7b", 32, 8, 128, 32, 4096),
    ("lfm2", 32, 8, 64, 70, None),
    ("qwen3next", 16, 2, 256, 36, None),
    ("ouro", 16, 16, 128, 8, None),
])
def test_tiled_prefill_lowers_for_the_tpu_at_the_cells_shapes(
        monkeypatch, cell, h, hkv, d, entries, window):
    """The tiled kernel at a cell's real widths lowers for the TPU (no chip,
    no compile: the Mosaic module is built from the kernel's jaxpr) under the
    names the benchmark's readers match."""
    import re

    from deepspeed_tpu.inference.v2.kernels import blocked_flash

    monkeypatch.setattr(blocked_flash, "on_tpu", lambda: True)
    rows, bs, nb = 1024, 128, entries + 8
    args = (jax.ShapeDtypeStruct((rows, h, d), jnp.bfloat16),
            jax.ShapeDtypeStruct((nb * bs, hkv * d), jnp.bfloat16),
            jax.ShapeDtypeStruct((nb * bs, hkv * d), jnp.bfloat16),
            jax.ShapeDtypeStruct((32, entries), jnp.int32),
            jax.ShapeDtypeStruct((rows,), jnp.int32),
            jax.ShapeDtypeStruct((rows,), jnp.int32))

    def read(q, kp, vp, tables, slot, pos):
        return blocked_flash.paged_prefill_attention(
            q, kp, vp, tables, slot, pos, block_size=bs, tile_q=128,
            window=window)

    text = jax.jit(read).trace(*args).lower(
        lowering_platforms=("tpu",)).as_text(debug_info=True)
    assert re.findall(r'@tpu_custom_call\(.*?kernel_name = "([^"]+)"',
                      text) == ["_prefill_kernel"]
    assert "jit(paged_prefill_attention)" in text
    # the pool is split into blocks as it lies: no copy in front of the call
    assert "stablehlo.transpose" not in text


@pytest.mark.parametrize("d,nb,single", [
    (128, 40, "paged_decode_attention"),      # D % 128 == 0: the walk,
    (128, 12, "paged_decode_attention"),      # whatever the pool's size
    (16, 40, "paged_attention"),              # big pool, small heads
    (16, 12, "_dense_pool_read"),             # tight pool, small heads
])
def test_two_segment_attention_routes_match_xla(monkeypatch, d, nb, single):
    """``_paged_attention`` on a two-segment buffer: the S single-token rows
    (slots in no order, pad rows at position -1) take the read a decode
    step of that pool takes, the tiles the tiled kernel; every real row
    equals the XLA gather composition."""
    from deepspeed_tpu.inference.v2 import kernels
    from deepspeed_tpu.inference.v2.modules import attention

    from deepspeed_tpu.inference.v2.ragged.kv_cache import flat_row

    rng = np.random.default_rng(19)
    bs, hkv, h, tile, S = 8, 2, 4, 16, 4
    # the row as BlockedKVCache stores it: flat where it is whole lane tiles
    row = (hkv * d,) if flat_row(jnp.float32, hkv, d) else (hkv, d)
    assert len(row) == (1 if d == 128 else 2)
    k_pool = jnp.asarray(rng.normal(size=(nb * bs,) + row).astype(np.float32))
    v_pool = jnp.asarray(rng.normal(size=(nb * bs,) + row).astype(np.float32))
    tables = jnp.asarray([[1, 2, 3, 4], [5, 6, 0, 0], [7, 8, 9, 0],
                          [10, 11, 0, 0]], jnp.int32)
    T = S + 3 * tile
    token_slot = np.zeros((T,), np.int32)
    token_pos = np.full((T,), -1, np.int32)
    # single-token rows: slot 2 at position 20, slot 0 at 30; two pad rows
    token_slot[0:2], token_pos[0:2] = (2, 0), (20, 30)
    # tiles: slot 1 positions 0..8 (one tile), slot 3 positions 3..15+5
    token_slot[S:S + 9], token_pos[S:S + 9] = 1, np.arange(0, 9)
    token_slot[S + 16:S + 34], token_pos[S + 16:S + 34] = 3, np.arange(1, 19)
    q = jnp.asarray(rng.normal(size=(T, h, d)).astype(np.float32))
    batch = {"block_tables": tables, "token_slot": jnp.asarray(token_slot),
             "token_pos": jnp.asarray(token_pos)}
    ran = []
    for mod, name in ((kernels, "paged_decode_attention"),
                      (kernels, "paged_attention"),
                      (kernels, "paged_prefill_attention"),
                      (attention, "_dense_pool_read")):
        def spy(*a, _f=getattr(mod, name), _n=name, **kw):
            ran.append(_n)
            return _f(*a, **kw)
        monkeypatch.setattr(mod, name, spy)
    for window in (None, 12):
        ref = attention._paged_attention(q, k_pool, v_pool, batch, bs,
                                            use_kernel=False, window=window)
        del ran[:]
        got = attention._paged_attention(q, k_pool, v_pool, batch, bs,
                                            use_kernel=True, window=window,
                                            prefill_tile=tile)
        assert ran == [single, "paged_prefill_attention"]
        real = token_pos >= 0
        assert got.shape == ref.shape and np.isfinite(np.asarray(got)).all()
        np.testing.assert_allclose(np.asarray(got)[real],
                                   np.asarray(ref)[real],
                                   rtol=2e-5, atol=2e-5)
    # a batch of single-token chunks only: no tiled segment at all
    head = {k: v[:S] if k != "block_tables" else v for k, v in batch.items()}
    del ran[:]
    got = attention._paged_attention(q[:S], k_pool, v_pool, head, bs,
                                        use_kernel=True, prefill_tile=tile)
    assert ran == [single]
    np.testing.assert_allclose(
        np.asarray(got)[:2],
        np.asarray(attention._paged_attention(
            q[:S], k_pool, v_pool, head, bs, use_kernel=False))[:2],
        rtol=2e-5, atol=2e-5)


def _tiled_scheduler(monkeypatch, params, cfg=CFG, token_budget=64,
                     num_blocks=None, prefix_cache=False):
    """A scheduler over an engine that packs the two-segment layout at
    tile 16 and attends through the Pallas kernels in interpret mode (the
    route ``_paged_attention`` takes on a TPU), with the calls of the two
    ``put`` kernels counted as they are traced."""
    from deepspeed_tpu.inference.v2 import kernels
    from deepspeed_tpu.inference.v2.modules import attention
    from deepspeed_tpu.observability import Tracer
    from deepspeed_tpu.serving import ContinuousBatchScheduler

    monkeypatch.setattr(attention, "on_tpu", lambda: True)
    calls = {"paged_prefill_attention": 0, "paged_attention": 0}
    for name in calls:
        def counted(*a, _f=getattr(kernels, name), _n=name, **kw):
            calls[_n] += 1
            return _f(*a, **kw)
        monkeypatch.setattr(kernels, name, counted)
    eng_cfg = RaggedInferenceEngineConfig.from_dict({
        "state_manager": {"max_ragged_batch_size": token_budget,
                          "max_ragged_sequence_count": 4,
                          "max_context": 64},
        "kv_cache": {"block_size": 8, "enable_prefix_cache": prefix_cache,
                     **({"num_blocks": num_blocks} if num_blocks else {})}})
    eng = InferenceEngineV2(RaggedLlama(cfg, 8), params, eng_cfg)
    eng.PREFILL_TILE = 16
    tr = Tracer()
    return ContinuousBatchScheduler(eng, tracer=tr), eng, tr, calls


def _tick_spans(tr):
    """[(kind of the tick, {span name: [records under it]})], oldest
    first."""
    recs = [r for r in tr.records() if r["ph"] == "X"]
    by_id = {r["span_id"]: r for r in recs}
    ticks = {r["span_id"]: (r["attrs"].get("kind"), {}) for r in recs
             if r["name"] == "tick"}
    for r in recs:
        up = r
        while up.get("parent") is not None:
            up = by_id[up["parent"]]
        if up is not r and up["span_id"] in ticks:
            ticks[up["span_id"]][1].setdefault(r["name"], []).append(r)
    return list(ticks.values())


def _sequential_reference(params, prompts, n_new, cfg=CFG):
    """Greedy tokens of each prompt served alone, on the XLA route of an
    engine that packs chunks back to back (budget 24: no whole tiles)."""
    eng = InferenceEngineV2(RaggedLlama(cfg, 8), params,
                            RaggedInferenceEngineConfig.from_dict({
                                "state_manager": {
                                    "max_ragged_batch_size": 24,
                                    "max_ragged_sequence_count": 4,
                                    "max_context": 64},
                                "kv_cache": {"block_size": 8}}))
    outs = []
    for i, p in enumerate(prompts):
        toks = [int(np.argmax(eng.put([i], [list(p)])[i]))]
        while len(toks) < n_new:
            toks.append(int(np.argmax(eng.put([i], [toks[-1:]])[i])))
        eng.flush([i])
        outs.append(toks)
    assert all(k[1] is None for k in eng.step_keys)
    return outs


#: name -> prompt lengths, the tick each arrives at, and what is special.
#: ``kinds``: tick kinds that must occur with a ``put`` forward.
_TWO_SEGMENT_CASES = {
    # (a) two sequences decode, then one long prompt arrives
    "decodes_beside_one_chunk": dict(lens=(5, 7, 40), arrive=(0, 0, 3),
                                     kinds={"mixed"}),
    # (b) a decode beside several chunks; 37 = two tiles and a 5-token
    # tail, and the 64-row tiled segment splits the 30-token prompt
    "decodes_beside_chunks_with_tail": dict(lens=(6, 37, 30),
                                            arrive=(0, 2, 2),
                                            kinds={"mixed"}),
    # (c) no decode, one chunk shorter than a tile
    "subtile_chunk_alone": dict(lens=(5,), arrive=(0,), kinds={"prefill"}),
    # the case the old rule (every chunk >= a tile) admitted
    "long_prompts_no_decode": dict(lens=(17, 20), arrive=(0, 0),
                                   kinds={"prefill"}),
    # (d) a sliding window shorter than the contexts
    "sliding_window": dict(lens=(6, 40, 23), arrive=(0, 3, 3),
                           kinds={"mixed"}, cfg=dict(sliding_window=12)),
    # (e) the second prompt shares two warm blocks with the first and
    # arrives while it decodes
    "shared_prefix_blocks": dict(lens=(20, 33), arrive=(0, 3), share=16,
                                 kinds={"mixed"}, prefix_cache=True),
    # (f) a pool of 8 blocks under four requests: one is preempted and
    # resumes (recompute) beside the decodes of the others
    "preempted_resumes_into_mixed_tick": dict(
        lens=(14, 15, 13, 12), arrive=(0, 0, 1, 1), num_blocks=9,
        kinds={"mixed"}, preempt=True, n_new=10),
}


@pytest.mark.parametrize("case", sorted(_TWO_SEGMENT_CASES))
def test_two_segment_batches_match_sequential(monkeypatch, case):
    """Every ``put`` forward of a tiled engine packs single-token rows in
    front of tile-aligned chunks: the generated tokens equal those of each
    prompt served alone on the XLA route, every program that ran is a
    tiled one, its prefill went through the tiled kernel and nothing
    through the token-grid kernel, and a tick is one forward."""
    spec = _TWO_SEGMENT_CASES[case]
    cfg = LlamaConfig.tiny(dtype=jnp.float32, **spec.get("cfg", {}))
    rng = np.random.default_rng(17)
    prompts = [rng.integers(0, cfg.vocab_size, size=(n,)).tolist()
               for n in spec["lens"]]
    for p in prompts[1:]:
        p[:spec.get("share", 0)] = prompts[0][:spec.get("share", 0)]
    n_new = spec.get("n_new", 6)
    params = _params()
    want = _sequential_reference(params, prompts, n_new, cfg)

    from deepspeed_tpu.serving import SamplingParams

    sched, eng, tr, calls = _tiled_scheduler(
        monkeypatch, params, cfg, num_blocks=spec.get("num_blocks"),
        prefix_cache=spec.get("prefix_cache", False))
    reqs, tick, preempted_at = [], 0, None
    while len(reqs) < len(prompts) or sched.num_pending:
        while len(reqs) < len(prompts) and spec["arrive"][len(reqs)] <= tick:
            reqs.append(sched.submit(prompts[len(reqs)], SamplingParams(
                greedy=True, max_new_tokens=n_new)))
        sched.step()
        if preempted_at is None and sched.metrics.preemptions:
            preempted_at = tick
        tick += 1
        assert tick < 500
    for r, w in zip(reqs, want):
        assert r.generated == w, (case, r.uid)

    puts = [k for k in eng.step_keys if not isinstance(k[0], str)]
    assert puts and all(k[1] == 16 and (k[0] - 4) % 16 == 0 for k in puts), \
        eng.step_keys
    assert calls["paged_prefill_attention"] and not calls["paged_attention"]
    forwards = [(kind, kids) for kind, kids in _tick_spans(tr)
                if "engine/ragged_step" in kids]
    assert spec["kinds"] <= {kind for kind, _ in forwards}
    for kind, kids in forwards:
        assert len(kids["engine/ragged_step"]) == 1, (case, kind)
        attrs = kids["engine/build_batch"][0]["attrs"]
        assert (attrs["bucket"], 16) in puts
        assert 0 < attrs["tokens"] <= min(attrs["bucket"], 64)
    if spec.get("preempt"):
        # the victim's recompute chunk ran beside the others' decodes
        assert preempted_at is not None and any(r.preemptions for r in reqs)
        mixed_after = [t for t in tr.records() if t["name"] == "tick"
                       and t["attrs"].get("kind") == "mixed"
                       and t["attrs"]["tick"] > preempted_at]
        assert mixed_after
    if spec.get("prefix_cache"):
        assert eng.prefix_cache_stats.hit_tokens == spec["share"]


def test_engine_tiled_generate_matches_v1():
    """``generate`` (``put`` + the scanned decode loop) on a tiled engine
    equals the v1 reference exactly.  The three chunks need 32 + 32 + 16
    rows of a 64-row tiled segment, so ``put`` loops a second forward for
    the last one (the scheduler never does: ``can_schedule`` counts the
    rows)."""
    rng = np.random.default_rng(17)
    prompts = [rng.integers(0, CFG.vocab_size, size=(n,)).tolist()
               for n in (17, 20, 3)]
    params = _params()
    ref = _v1_reference_tokens(params, prompts, n_new=5)

    eng = _v2_engine(params, token_budget=64, block_size=8, max_context=64)
    eng.PREFILL_TILE = 16
    out = eng.generate(prompts, max_new_tokens=5)
    assert not eng.can_schedule([1, 2, 3], [17, 20, 3])
    assert eng.can_schedule([1, 2, 3], [17, 20, 1])
    assert [k for k in eng.step_keys if not isinstance(k[0], str)] == \
        [(4 + 64, 16), (4 + 16, 16)]
    for got, want in zip(out, ref):
        np.testing.assert_array_equal(got, np.asarray(want))


# --------------------------------------------------------------------- #
# put(greedy=True): the step program's own argmax, one int32 a row
# --------------------------------------------------------------------- #
def _tied_params(params):
    """``params`` with every odd column of the head a copy of the even one
    before it: each row's maximum is held by two indices, bit for bit."""
    kernel = np.array(params["lm_head"]["kernel"])
    kernel[:, 1::2] = kernel[:, 0::2]
    return {**params, "lm_head": {"kernel": jnp.asarray(kernel)}}


@pytest.mark.parametrize("tile", [None, 16], ids=["packed", "tiled"])
@pytest.mark.parametrize("tied", [False, True], ids=["distinct", "tied"])
def test_put_greedy_is_the_argmax_of_put_logits(tile, tied):
    """Two engines fed the same ragged batches (a 20-token prompt in two
    chunks beside a 5-token one, then a token each beside a third prompt):
    the tokens one returns are ``np.argmax`` of the rows the other returns,
    in both layouts; with tied maxima the first index wins on both sides."""
    params = _tied_params(_params()) if tied else _params()
    rng = np.random.default_rng(40)
    a, b, c = (rng.integers(0, CFG.vocab_size, size=(n,)).tolist()
               for n in (20, 5, 9))
    engines = []
    for _ in range(2):
        eng = _v2_engine(params, token_budget=64 if tile else 16)
        if tile:
            eng.PREFILL_TILE = tile
        engines.append(eng)
    by_logits, by_tokens = engines
    feeds = [([1, 2], [a, b])]
    for _ in range(3):
        rows = by_logits.put(*feeds[-1])
        toks = by_tokens.put(*feeds[-1], greedy=True)
        assert set(toks) == set(rows)
        for uid, row in rows.items():
            assert type(toks[uid]) is int
            assert toks[uid] == int(np.argmax(row)), (uid, len(feeds))
            if tied:
                assert toks[uid] % 2 == 0 and \
                    row[toks[uid]] == row[toks[uid] + 1]
        feeds.append(([1, 2, 3][:len(feeds) + 2],
                      ([[toks[1]], [toks[2]]] + [c])[:len(feeds) + 2]))
    # the same programs, built once: asking for the other output of a
    # bucket compiles nothing
    assert by_logits.step_keys == by_tokens.step_keys
    by_tokens.put([1], [[7]])
    keys = by_tokens.step_keys
    by_tokens.put([1], [[8]], greedy=True)
    assert by_tokens.step_keys == keys
    assert [by_tokens._steps[k]._cache_size() for k in keys] == \
        [1] * len(keys)


def test_put_greedy_without_sync_returns_device_tokens():
    eng = _v2_engine(_params())
    out = eng.put([1], [[3, 4, 5]], sync=False, greedy=True)
    assert isinstance(out[1], jax.Array) and out[1].dtype == jnp.int32
    want = _v2_engine(_params()).put([1], [[3, 4, 5]])
    assert int(out[1]) == int(np.argmax(want[1]))


def test_ragged_step_programs_keep_their_names_and_outputs():
    """One program a ``(rows, tile)`` key under the name it had, returning
    the logits, their argmax and the donated cache."""
    eng = _v2_engine(_params(), token_budget=64)
    eng.PREFILL_TILE = 16
    eng.put([1], [list(range(1, 20))])
    eng.put([1], [[5]], greedy=True)
    assert eng.step_keys == [(4 + 32, 16), (4, 16)]
    assert [eng._steps[k].__name__ for k in eng.step_keys] == \
        ["ragged_step_T36_tiled", "ragged_step_T4_tiled"]
    lowered = eng.lower_step((4, 16))
    logits, nxt, _cache = lowered.out_info
    assert logits.shape == (4, CFG.vocab_size)
    assert nxt.shape == (4,) and nxt.dtype == jnp.int32
    assert "sample_argmax" in lowered.as_text(debug_info=True)
