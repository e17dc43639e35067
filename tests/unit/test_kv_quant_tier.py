"""int8 block-quantized KV cache + host cold tier tests.

Parity contract (the PR-9 convention): bit-parity asserts run on f32
activations — every COMPOSITION (decode paths, speculative verify,
COW fork, preempt→resume, spool→restore, disaggregated handoff) must be
bit-identical WITHIN the int8-KV arm, because all of them read the same
deterministic quantized records.  Across dtypes (int8 KV vs f32 KV) the
quantization error is real, so quality is asserted as logits closeness
plus leading-token agreement, not unbounded token parity.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference.v2 import (InferenceEngineV2,
                                        RaggedInferenceEngineConfig)
from deepspeed_tpu.inference.v2.config_v2 import KVCacheConfig
from deepspeed_tpu.inference.v2.model_implementations import RaggedLlama
from deepspeed_tpu.inference.v2.ragged import (BlockedKVCache, HostKVTier,
                                               dequantize_kv, quantize_kv)
from deepspeed_tpu.inference.v2.ragged.kv_cache import resolve_kv_dtype
from deepspeed_tpu.models import LlamaConfig, LlamaForCausalLM
from deepspeed_tpu.serving import (ContinuousBatchScheduler, RequestState,
                                   SamplingParams, sample_one)

CFG = LlamaConfig.tiny(dtype=jnp.float32)


@pytest.fixture(scope="module")
def params():
    return LlamaForCausalLM(CFG).init(
        jax.random.key(0), np.zeros((1, 4), np.int32))["params"]


def _engine(params, kv_dtype=None, host_tier=False, token_budget=32,
            block_size=8, max_context=64, max_seqs=4, num_blocks=None,
            prefix_cache=True, host_tier_bytes=None):
    cfg = RaggedInferenceEngineConfig.from_dict({
        "state_manager": {"max_ragged_batch_size": token_budget,
                          "max_ragged_sequence_count": max_seqs,
                          "max_context": max_context},
        "kv_cache": {"block_size": block_size,
                     "enable_prefix_cache": prefix_cache,
                     **({"dtype": kv_dtype} if kv_dtype else {}),
                     **({"host_tier": True} if host_tier else {}),
                     **({"host_tier_bytes": host_tier_bytes}
                        if host_tier_bytes is not None else {}),
                     **({"num_blocks": num_blocks}
                        if num_blocks is not None else {})},
    })
    return InferenceEngineV2(RaggedLlama(CFG, block_size), params, cfg)


def _greedy_chain(eng, uid, prompt, n_new):
    logits = eng.put([uid], [list(prompt)])
    toks = [int(np.argmax(logits[uid]))]
    for _ in range(n_new - 1):
        logits = eng.put([uid], [[toks[-1]]])
        toks.append(int(np.argmax(logits[uid])))
    eng.flush([uid])
    return toks


# --------------------------------------------------------------------- #
# Quantizer + cache structure units
# --------------------------------------------------------------------- #
def test_quantize_kv_roundtrip_and_determinism():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((64, 2, 32)).astype(np.float32))
    q, s = quantize_kv(x)
    assert q.dtype == jnp.int8 and s.shape == (64, 2)
    back = dequantize_kv(q, s)
    # per-head absmax/127: error bounded by half a quantization step
    step = np.asarray(s)[..., None]
    assert float(jnp.max(jnp.abs(back - x))) <= float(np.max(step)) * 0.5 + 1e-7
    # deterministic: identical input -> bitwise identical records
    q2, s2 = quantize_kv(jnp.asarray(np.asarray(x)))
    assert np.array_equal(np.asarray(q), np.asarray(q2))
    assert np.array_equal(np.asarray(s), np.asarray(s2))
    # all-zero rows quantize to zero payload with the safe 1.0 scale
    qz, sz = quantize_kv(jnp.zeros((4, 2, 8)))
    assert np.all(np.asarray(qz) == 0) and np.all(np.asarray(sz) == 1.0)


def test_blocked_kv_cache_int8_layout_and_bytes():
    c8 = BlockedKVCache(2, 4, 8, 2, 32, dtype="int8")
    assert c8.quantized
    layer = c8.cache["layer_0"]
    assert set(layer) == {"k", "v", "k_scale", "v_scale"}
    assert layer["k"].dtype == jnp.int8
    assert layer["k_scale"].shape == (32, 2)
    # dtype-aware accounting: int8 payload + fp32 scale per (row, head)
    assert c8.per_token_bytes == 2 * 2 * 2 * (32 + 4)
    cb = BlockedKVCache(2, 4, 8, 2, 32, dtype="bf16")
    assert not cb.quantized and cb.per_token_bytes == 2 * 2 * 2 * 32 * 2
    with pytest.raises(ValueError, match="not understood"):
        BlockedKVCache(2, 4, 8, 2, 32, dtype="int3")
    assert resolve_kv_dtype("bfloat16") == jnp.bfloat16


def test_int8_block_ops_carry_scales_bitexact():
    """copy_block / gather_blocks / scatter_blocks move payload AND
    scale records together, bit-exactly."""
    c = BlockedKVCache(2, 5, 4, 2, 16, dtype="int8")
    rng = np.random.default_rng(1)

    def fill(leaf):
        if leaf.dtype == jnp.int8:
            return jnp.asarray(rng.integers(-127, 128, size=leaf.shape),
                               jnp.int8)
        return jnp.asarray(rng.random(leaf.shape).astype(np.float32))

    c.cache = jax.tree_util.tree_map(fill, c.cache)
    before = jax.device_get(c.cache)
    c.copy_block(1, 3)
    after = jax.device_get(c.cache)
    for lname, lv in after.items():
        for leaf in ("k", "v", "k_scale", "v_scale"):
            np.testing.assert_array_equal(
                lv[leaf][3 * 4:4 * 4], before[lname][leaf][1 * 4:2 * 4])
    payload = c.gather_blocks([1, 2])
    c2 = BlockedKVCache(2, 5, 4, 2, 16, dtype="int8")
    c2.scatter_blocks([2, 4], payload)
    back = c2.gather_blocks([2, 4])
    for a, b in zip(jax.tree_util.tree_leaves(payload),
                    jax.tree_util.tree_leaves(back)):
        np.testing.assert_array_equal(a, b)


# --------------------------------------------------------------------- #
# Config plumbing + guards
# --------------------------------------------------------------------- #
def test_config_dtype_and_host_tier_plumbing(params):
    eng = _engine(params, kv_dtype="int8", host_tier=True)
    sm = eng.state_manager
    assert sm.kv_cache.quantized and sm.host_tier is not None
    assert sm.prefix_cache.spool_fn is not None
    with pytest.raises(ValueError, match="not understood"):
        KVCacheConfig.from_dict({"dtype": "fp7"})
    with pytest.raises(ValueError, match="enable_prefix_cache"):
        KVCacheConfig.from_dict({"host_tier": True})
    with pytest.raises(ValueError, match="enable_prefix_cache"):
        _engine(params, kv_dtype="int8", host_tier=True,
                prefix_cache=False)


def test_engine_rejects_int8_on_unsupporting_model(params):
    class NoQuantLlama(RaggedLlama):
        supports_quantized_kv = False

    cfg = RaggedInferenceEngineConfig.from_dict({
        "state_manager": {"max_ragged_batch_size": 32,
                          "max_ragged_sequence_count": 4,
                          "max_context": 64},
        "kv_cache": {"block_size": 8, "dtype": "int8"},
    })
    with pytest.raises(ValueError, match="int8"):
        InferenceEngineV2(NoQuantLlama(CFG, 8), params, cfg)


# --------------------------------------------------------------------- #
# int8-vs-f32 quality + intra-int8 parity across decode paths
# --------------------------------------------------------------------- #
def test_int8_vs_f32_logits_close_and_leading_tokens_agree(params):
    rng = np.random.default_rng(2)
    prompt = rng.integers(0, CFG.vocab_size, size=(17,)).tolist()
    e32 = _engine(params)
    e8 = _engine(params, kv_dtype="int8")
    l32 = e32.put([1], [prompt])[1]
    l8 = e8.put([1], [prompt])[1]
    denom = float(np.max(np.abs(l32))) + 1e-9
    rel = float(np.max(np.abs(l32 - l8))) / denom
    assert rel < 0.05, f"int8 KV perturbed prompt logits by {rel:.3%}"
    t32 = _greedy_chain(e32, 2, prompt, 4)
    t8 = _greedy_chain(e8, 2, prompt, 4)
    # a random-init tiny model has near-tied logits; leading agreement
    # is the honest cross-dtype claim (full parity is intra-arm only)
    assert t32[:2] == t8[:2]
    e32.flush([1]), e8.flush([1])


def test_int8_put_vs_decode_step_bit_parity(params):
    """The put()-path and the device-resident decode_step path read the
    same quantized records — greedy tokens are bit-identical."""
    rng = np.random.default_rng(3)
    prompt = rng.integers(0, CFG.vocab_size, size=(14,)).tolist()
    ref = _greedy_chain(_engine(params, kv_dtype="int8"), 1, prompt, 6)
    eng = _engine(params, kv_dtype="int8")
    logits = eng.put([1], [prompt])
    toks = [int(np.argmax(logits[1]))]
    _, nxt = eng.decode_step([1], [toks[-1]], greedy=True)
    for _ in range(4):
        toks.append(int(jax.device_get(nxt)[0]))
        _, nxt = eng.decode_step([1], nxt, greedy=True)
    toks.append(int(jax.device_get(nxt)[0]))
    assert toks == ref


@pytest.mark.parametrize("k", [1, 3, 5])
def test_int8_verify_step_bit_parity(params, k):
    """Speculative verify over the quantized cache: K candidate logits
    rows equal K sequential decode steps bitwise (f32 activations) —
    the verify program quantizes the same values to the same records."""
    rng = np.random.default_rng(4)
    prompt = rng.integers(0, CFG.vocab_size, size=(13,)).tolist()
    seq_eng = _engine(params, kv_dtype="int8")
    logits = seq_eng.put([1], [prompt])
    cur = int(np.argmax(logits[1]))
    feed = [cur]
    ref_rows = []
    for _ in range(k):
        lg = seq_eng.put([1], [[feed[-1]]])
        ref_rows.append(np.asarray(lg[1], np.float32))
        feed.append(int(np.argmax(lg[1])))
    ver_eng = _engine(params, kv_dtype="int8")
    ver_eng.put([1], [prompt])
    rows = np.asarray(jax.device_get(
        ver_eng.verify_step([1], [feed[:k]])), np.float32)[0]
    for i in range(k):
        np.testing.assert_array_equal(rows[i], ref_rows[i])
    # commit + rollback leaves allocator state where sequential decode is
    ver_eng.commit_verified(1, feed[:k])
    assert (ver_eng.state_manager.get_sequence(1).seen_tokens
            == seq_eng.state_manager.get_sequence(1).seen_tokens)


def test_int8_cow_fork_parity(params):
    """Partial-block prefix attach COW-forks on the quantized cache —
    payload + scales copied together; warm run stays bit-exact."""
    rng = np.random.default_rng(5)
    prompt = rng.integers(0, CFG.vocab_size, size=(21,)).tolist()  # 2.6 blk
    ref = _greedy_chain(_engine(params, kv_dtype="int8",
                                prefix_cache=False), 9, prompt, 6)
    eng = _engine(params, kv_dtype="int8")
    cold = _greedy_chain(eng, 1, prompt, 6)
    warm = _greedy_chain(eng, 2, prompt, 6)
    assert cold == ref and warm == ref
    assert eng.state_manager.prefix_cache.stats.hits == 1


def test_int8_stochastic_parity_warm_vs_cold(params):
    """(seed, uid, position)-keyed sampling over bit-identical quantized
    logits draws bit-identical tokens, cold vs cache-hit."""
    rng = np.random.default_rng(6)
    prompt = rng.integers(0, CFG.vocab_size, size=(18,)).tolist()
    sp = SamplingParams(greedy=False, temperature=0.7, top_k=8, seed=42)

    def chain(eng, uid):
        logits = eng.put([uid], [list(prompt)])
        toks = [sample_one(logits[uid], sp, 0, uid=7)]
        for i in range(4):
            logits = eng.put([uid], [[toks[-1]]])
            toks.append(sample_one(logits[uid], sp, i + 1, uid=7))
        eng.flush([uid])
        return toks

    eng = _engine(params, kv_dtype="int8")
    assert chain(eng, 1) == chain(eng, 2)


def test_int8_preempt_resume_parity(params):
    """flush_to_host -> recompute resume on the int8 arm reproduces the
    unpreempted continuation token-for-token (deterministic quantizer:
    the re-prefilled records are bitwise the originals)."""
    rng = np.random.default_rng(7)
    prompt = rng.integers(0, CFG.vocab_size, size=(12,)).tolist()
    ref = _greedy_chain(_engine(params, kv_dtype="int8"), 1, prompt, 8)
    eng = _engine(params, kv_dtype="int8")
    logits = eng.put([2], [prompt])
    toks = [int(np.argmax(logits[2]))]
    for _ in range(3):
        logits = eng.put([2], [[toks[-1]]])
        toks.append(int(np.argmax(logits[2])))
    eng.flush_to_host([2])                       # preempt (drop KV)
    hist = prompt + toks
    logits = eng.resume(2, hist)                 # recompute re-prefill
    toks.append(int(np.argmax(logits[2])))
    for _ in range(3):
        logits = eng.put([2], [[toks[-1]]])
        toks.append(int(np.argmax(logits[2])))
    assert toks == ref


# --------------------------------------------------------------------- #
# Host cold tier: spool -> restore bit-exactness + accounting
# --------------------------------------------------------------------- #
def _grow_session(eng, uid, prompt, n_new):
    logits = eng.put([uid], [prompt])
    toks = [int(np.argmax(logits[uid]))]
    for _ in range(n_new - 1):
        logits = eng.put([uid], [[toks[-1]]])
        toks.append(int(np.argmax(logits[uid])))
    return prompt + toks


def test_spool_restore_bit_exact_and_parity(params):
    rng = np.random.default_rng(8)
    eng = _engine(params, kv_dtype="int8", host_tier=True, num_blocks=10,
                  token_budget=64)
    sm = eng.state_manager
    pA = rng.integers(0, CFG.vocab_size, size=(16,)).tolist()
    histA = _grow_session(eng, 1, pA, 9)         # 24 seen -> 3 full blocks
    pre = sm.kv_cache.gather_blocks(list(sm.get_sequence(1).blocks)[:3])
    eng.flush([1])                               # idle: warm in tree
    assert sm.prefix_cache.evictable_blocks == 3
    # two 32-token sessions force eviction of A's cold blocks -> spooled
    for uid, seed in ((2, 5), (3, 6)):
        p = np.random.default_rng(seed).integers(
            0, CFG.vocab_size, size=(32,)).tolist()
        eng.put([uid], [p])
        eng.flush([uid])
    st = sm.host_tier.stats
    assert len(sm.host_tier) > 0 and st.spooled_blocks >= 2
    assert sm.host_tier.bytes > 0
    # resume: attach restores spooled blocks bit-exactly
    cached = eng.attach_prefix(1, histA)
    assert cached == 24 and st.restored_blocks >= 2
    # batched restore: ONE scatter dispatch+sync moved every contiguous
    # tier hit — one latency sample per CALL, blocks-per-call histogram
    # accounting for every restored block
    assert len(st.restore_s) == 1
    assert sum(st.restore_blocks_per_call) == st.restored_blocks
    assert st.restore_blocks_pct(100) == float(st.restored_blocks)
    post = sm.kv_cache.gather_blocks(list(sm.get_sequence(1).blocks)[:3])
    for a, b in zip(jax.tree_util.tree_leaves(pre),
                    jax.tree_util.tree_leaves(post)):
        np.testing.assert_array_equal(a, b)
    # continuation equals a never-evicted straight-line run
    logits = eng.put([1], [histA[cached:]])
    ref_eng = _engine(params, kv_dtype="int8", num_blocks=33)
    ref = ref_eng.put([1], [histA])
    np.testing.assert_array_equal(np.asarray(logits[1]),
                                  np.asarray(ref[1]))
    # occupancy gauges carry the tier surface
    occ = eng.occupancy()
    assert occ["observability/kv_spooled_blocks"] == float(
        st.spooled_blocks)
    assert occ["observability/kv_restored_blocks"] == float(
        st.restored_blocks)
    assert occ["observability/kv_restore_p95_s"] >= 0.0


def test_tier_refcount_and_evictable_lockstep(params):
    """Allocator refcounts and the O(1) evictable counter stay in
    lockstep through the spool -> restore -> re-evict cycle."""
    rng = np.random.default_rng(9)
    eng = _engine(params, kv_dtype="int8", host_tier=True, num_blocks=10,
                  token_budget=64)
    sm = eng.state_manager
    alloc = sm.allocator
    pA = rng.integers(0, CFG.vocab_size, size=(16,)).tolist()
    histA = _grow_session(eng, 1, pA, 9)
    eng.flush([1])
    free0 = alloc.free_blocks
    # pressure: spool A's warm blocks (two 4-block sessions exceed the
    # 6 free blocks left beside A's 3 warm ones)
    for uid, seed in ((2, 20), (3, 21)):
        p = np.random.default_rng(seed).integers(
            0, CFG.vocab_size, size=(32,)).tolist()
        eng.put([uid], [p])
        eng.flush([uid])
    assert sm.host_tier.stats.spooled_blocks >= 1
    # restore on attach: tree holds rc1, sequence acquire makes rc2
    eng.attach_prefix(1, histA)
    seq = sm.get_sequence(1)
    for b in seq.blocks[:seq.shared_blocks]:
        assert alloc.refcount(b) == 2
    # shared blocks are pinned: not evictable while the sequence lives
    pinned = sm.prefix_cache.evictable_blocks
    eng.flush([1])
    assert sm.prefix_cache.evictable_blocks >= pinned
    # evictable counter equals brute-force count of rc1 watched blocks
    brute = sum(1 for b in list(alloc._watched)
                if alloc.refcount(b) == 1)
    assert sm.prefix_cache.evictable_blocks == brute
    assert alloc.free_blocks <= free0


def test_restore_under_full_pool_never_recycles_the_match(params):
    """A restore's allocation runs with the in-HBM match already
    acquired (rc2), so eviction under a FULL pool can never recycle a
    block the very same attach is about to use — unprotected, the
    match's rc1 leaf is the eviction victim and the restore scatters
    over it (aliased blocks / acquire-of-free)."""
    rng = np.random.default_rng(27)
    eng = _engine(params, kv_dtype="int8", host_tier=True, num_blocks=10,
                  token_budget=64)
    sm = eng.state_manager
    alloc = sm.allocator
    pA = rng.integers(0, CFG.vocab_size, size=(16,)).tolist()
    histA = _grow_session(eng, 1, pA, 9)         # 24 seen -> 3 full blocks
    eng.flush([1])                               # tree-held, rc1 x3
    assert sm.prefix_cache.evict(2) == 2         # deepest 2 spool to host
    assert len(sm.host_tier) == 2
    a0 = sm.prefix_cache.match_blocks(histA)[0]  # the surviving match
    hoard = alloc.allocate(alloc.free_blocks)    # pool now FULL
    cached = eng.attach_prefix(4, histA)
    seq = sm.get_sequence(4)
    # the match attached and was never evicted/recycled mid-restore
    assert cached == 8 and seq.blocks == [a0]
    assert alloc.refcount(a0) == 2
    # restores found no room: payloads put back intact, not recounted
    assert len(sm.host_tier) == 2
    assert sm.host_tier.stats.restored_blocks == 0
    assert sm.host_tier.stats.spooled_blocks == 2
    # release the pressure: the SAME tier entries now restore fully and
    # the continuation equals a never-evicted straight-line run
    eng.flush([4])
    alloc.free(hoard)
    assert eng.attach_prefix(5, histA) == 24
    assert sm.host_tier.stats.restored_blocks == 2
    logits = eng.put([5], [histA[24:]])
    ref_eng = _engine(params, kv_dtype="int8", num_blocks=33)
    ref = ref_eng.put([5], [histA])
    np.testing.assert_array_equal(np.asarray(logits[5]),
                                  np.asarray(ref[5]))


def test_tier_byte_budget_drops_oldest():
    tier = HostKVTier(max_bytes=100)
    a = {"layer_0": {"k": np.zeros(40, np.int8)}}
    tier.put((1,), a)
    tier.put((2,), a)
    assert tier.bytes == 80 and len(tier) == 2
    tier.put((3,), a)                    # 120 > 100: oldest drops
    assert tier.bytes == 80 and len(tier) == 2
    assert tier.stats.dropped_blocks == 1
    assert tier.get((1,)) is None        # (1,) was the LRU victim
    assert tier.get((2,)) is not None


def test_tier_miss_falls_back_to_recompute(params):
    """A zero-budget tier drops every spool immediately — resume then
    recomputes through the normal prefill path, still token-exact."""
    rng = np.random.default_rng(10)
    eng = _engine(params, kv_dtype="int8", host_tier=True, num_blocks=10,
                  token_budget=64, host_tier_bytes=1)
    sm = eng.state_manager
    pA = rng.integers(0, CFG.vocab_size, size=(16,)).tolist()
    histA = _grow_session(eng, 1, pA, 9)
    eng.flush([1])
    # three 4-block sessions push all three of A's blocks out of the
    # 10-block pool (two leave one of them warm: a partial hit, not a miss)
    for uid, seed in ((2, 22), (3, 23), (4, 24)):
        p = np.random.default_rng(seed).integers(
            0, CFG.vocab_size, size=(32,)).tolist()
        eng.put([uid], [p])
        eng.flush([uid])
    assert sm.host_tier.stats.dropped_blocks >= 3
    assert sm.host_tier.stats.restored_blocks == 0
    got = eng.put([1], [histA])          # full recompute (miss path)
    assert sm.prefix_cache.stats.hit_tokens == 0
    assert sm.host_tier.stats.restored_blocks == 0
    # same token budget as the engine under test: a bitwise comparison
    # holds only between programs of the same padding bucket
    ref_eng = _engine(params, kv_dtype="int8", num_blocks=33,
                      token_budget=64)
    ref = ref_eng.put([1], [histA])
    np.testing.assert_array_equal(np.asarray(got[1]), np.asarray(ref[1]))


def test_disaggregated_handoff_carries_int8_scales(params):
    """flush_to_host(include_kv=True) -> resume(kv_state=...) between
    two int8 engines: the payload carries scale records, so the target's
    next-token logits equal the colocated run bitwise."""
    rng = np.random.default_rng(11)
    prompt = rng.integers(0, CFG.vocab_size, size=(15,)).tolist()
    src = _engine(params, kv_dtype="int8")
    logits = src.put([4], [prompt])
    tok = int(np.argmax(logits[4]))
    snap = src.flush_to_host([4], include_kv=True)[4]
    assert "kv" in snap and any(
        "scale" in k for k in snap["kv"]["layer_0"])
    dst = _engine(params, kv_dtype="int8")
    dst.resume(4, prompt, kv_state=snap)
    got = dst.put([4], [[tok]])
    ref_eng = _engine(params, kv_dtype="int8")
    ref_eng.put([5], [prompt])
    ref = ref_eng.put([5], [[tok]])
    np.testing.assert_array_equal(np.asarray(got[4]), np.asarray(ref[5]))


# --------------------------------------------------------------------- #
# Steady-state decode stays trace-clean with quantized + tiered cache
# --------------------------------------------------------------------- #
def test_traceguard_steady_decode_int8_tier(params):
    """Warmed decode ticks over the quantized + tiered cache: 0
    recompiles, and no host syncs beyond what the identical bf16-cache
    scheduler performs (the tier only acts on the allocation path under
    pressure, never on a pressure-free decode tick)."""
    from deepspeed_tpu.analysis.trace_guard import TraceGuard

    def run(kv_dtype, host_tier):
        eng = _engine(params, kv_dtype=kv_dtype, host_tier=host_tier,
                      num_blocks=33, max_context=64)
        sched = ContinuousBatchScheduler(eng)
        rng = np.random.default_rng(12)
        for _ in range(2):
            sched.submit(rng.integers(0, CFG.vocab_size,
                                      size=(8,)).tolist(),
                         sampling=SamplingParams(greedy=True,
                                                 max_new_tokens=16))
        for _ in range(32):
            sched.step()
            running = list(sched._running.values())
            if len(running) == 2 and all(
                    r.state is RequestState.DECODE for r in running):
                break
        for _ in range(2):
            sched.step()                 # warm the decode programs
        with TraceGuard(max_compiles=0, d2h="disallow",
                        label=f"decode tick ({kv_dtype})") as tg:
            for _ in range(4):
                assert sched.step()
        sched.run_until_idle()
        return tg

    base = run(None, False)              # f32 cache, no tier
    tiered = run("int8", True)
    assert tiered.compiles == 0
    assert tiered.host_syncs == base.host_syncs


# --------------------------------------------------------------------- #
# Observability satellite: dtype-aware bytes
# --------------------------------------------------------------------- #
def test_occupancy_bytes_dtype_aware(params):
    from deepspeed_tpu.observability.memory import kv_occupancy

    e8 = _engine(params, kv_dtype="int8", num_blocks=17)
    occ = kv_occupancy(e8.state_manager)
    ptb = e8.state_manager.kv_cache.per_token_bytes
    assert ptb == 2 * CFG.num_hidden_layers * CFG.num_key_value_heads \
        * (CFG.head_dim + 4)
    assert occ["observability/kv_pool_bytes"] == float(17 * 8 * ptb)
    # same geometry at bf16 is bigger per token
    eb = _engine(params, kv_dtype="bf16", num_blocks=17)
    assert eb.state_manager.kv_cache.per_token_bytes > ptb


# --------------------------------------------------------------------- #
# Session mix: the capacity the int8 pool + host tier buy in one byte
# budget, through the engine and its scheduler
# --------------------------------------------------------------------- #
def _resident_sessions(params, kv_dtype, host_tier, budget_bytes,
                       max_sessions=8, block_size=8):
    """Admit sessions one at a time; every second admission the least
    recently touched session comes back with its whole history.  A
    session is resident while its resume is served from warm or
    restorable KV: the arm stops at the first recompute or preemption."""
    per_tok = BlockedKVCache(CFG.num_hidden_layers, 1, block_size,
                             CFG.num_key_value_heads, CFG.head_dim,
                             kv_dtype).per_token_bytes
    num_blocks = budget_bytes // (block_size * per_tok) + 1
    eng = _engine(params, kv_dtype=kv_dtype, host_tier=host_tier,
                  token_budget=64, block_size=block_size,
                  num_blocks=num_blocks)
    sched = ContinuousBatchScheduler(eng)
    stats = eng.state_manager.prefix_cache.stats
    rng = np.random.default_rng(5)
    sampling = SamplingParams(greedy=True, max_new_tokens=4)

    def turn(prompt):
        req = sched.submit(prompt, sampling=sampling)
        sched.run_until_idle()
        assert req.state == RequestState.FINISHED, req.finish_reason
        return list(req.prompt) + list(req.generated)

    histories, touched, recompute, resident = {}, {}, 0, 0
    for s in range(max_sessions):
        histories[s] = turn(rng.integers(0, CFG.vocab_size, 24).tolist())
        touched[s] = 2 * s
        if s % 2:
            old = min(touched, key=touched.get)
            prev = histories[old]
            # full blocks whose KV was written (the last emitted token's
            # never was): a warm resume re-attaches exactly these
            expected = (len(prev) - 1) // block_size * block_size
            before = stats.hit_tokens
            histories[old] = turn(
                prev + rng.integers(0, CFG.vocab_size, 8).tolist())
            touched[old] = 2 * s + 1
            recompute += max(0, expected - (stats.hit_tokens - before))
        if recompute or sched.metrics.snapshot()["preemptions"]:
            break
        resident = s + 1
    tier = eng.state_manager.host_tier
    return {"resident": resident, "kv_blocks": num_blocks,
            "recompute_tokens": recompute,
            "preemptions": sched.metrics.snapshot()["preemptions"],
            "spooled_blocks": tier.stats.spooled_blocks if tier else 0,
            "restored_blocks": tier.stats.restored_blocks if tier else 0}


def test_session_mix_int8_tier_holds_more_sessions(params):
    """An int8 pool with the host tier holds at least as many resident
    sessions in the same HBM byte budget as bf16 without it, with no
    recompute and no preemption."""
    budget = 12 * 8 * BlockedKVCache(
        CFG.num_hidden_layers, 1, 8, CFG.num_key_value_heads,
        CFG.head_dim, "bf16").per_token_bytes
    base = _resident_sessions(params, "bf16", False, budget)
    treat = _resident_sessions(params, "int8", True, budget)
    assert treat["recompute_tokens"] == 0 and treat["preemptions"] == 0
    assert treat["resident"] >= base["resident"]
    # int8 fits more blocks into the same byte budget
    assert treat["kv_blocks"] > base["kv_blocks"]
    # the baseline ran out of pool; the treatment spooled and restored
    assert base["recompute_tokens"] > 0 and base["resident"] < 8
    assert treat["resident"] == 8
    assert treat["spooled_blocks"] > 0 and treat["restored_blocks"] > 0


# --------------------------------------------------------------------- #
# Batched tier traffic: N blocks move with O(1) gather/scatter
# dispatches (ROADMAP item 4e) — and stay bit-exact doing it
# --------------------------------------------------------------------- #
def test_batched_spool_restore_single_dispatch_and_bit_exact(params):
    """A multi-block eviction hands the spool hook its whole victim
    list (ONE gather_blocks dispatch + sync), and a multi-block resume
    scatters every contiguous tier hit in ONE scatter_blocks call —
    the per-block serial dispatch cost (~3-5 ms each) is gone.  Call
    counts are asserted by instrumenting the cache's gather/scatter
    entry points; bit-exactness by comparing the restored continuation
    against a never-evicted straight-line run."""
    rng = np.random.default_rng(33)
    eng = _engine(params, kv_dtype="int8", host_tier=True, num_blocks=10,
                  token_budget=64)
    sm = eng.state_manager
    calls = {"gather": [], "scatter": []}
    real_gather = sm.kv_cache.gather_blocks
    real_scatter = sm.kv_cache.scatter_blocks

    def counting_gather(blocks):
        calls["gather"].append(list(blocks))
        return real_gather(blocks)

    def counting_scatter(blocks, payload):
        calls["scatter"].append(list(blocks))
        return real_scatter(blocks, payload)

    sm.kv_cache.gather_blocks = counting_gather
    sm.kv_cache.scatter_blocks = counting_scatter

    pA = rng.integers(0, CFG.vocab_size, size=(16,)).tolist()
    histA = _grow_session(eng, 1, pA, 9)         # 24 seen -> 3 full blocks
    eng.flush([1])                               # tree-held, rc1 x3
    calls["gather"].clear()
    # one explicit eviction of 3 blocks == exactly ONE gather dispatch
    assert sm.prefix_cache.evict(3) == 3
    assert len(calls["gather"]) == 1 and len(calls["gather"][0]) == 3
    st = sm.host_tier.stats
    assert len(sm.host_tier) == 3 and st.spooled_blocks == 3
    assert list(st.spool_blocks_per_call) == [3]
    assert len(st.spool_s) == 1                  # one latency sample/call

    # resume: all 3 contiguous tier hits restore in ONE scatter call
    calls["scatter"].clear()
    cached = eng.attach_prefix(2, histA)
    assert cached == 24 and st.restored_blocks == 3
    assert len(calls["scatter"]) == 1 and len(calls["scatter"][0]) == 3
    assert list(st.restore_blocks_per_call) == [3]
    assert len(st.restore_s) == 1

    # bit-exact: the batched spool->restore round trip changes nothing
    logits = eng.put([2], [histA[cached:]])
    ref_eng = _engine(params, kv_dtype="int8", num_blocks=33)
    ref = ref_eng.put([2], [histA])
    np.testing.assert_array_equal(np.asarray(logits[2]),
                                  np.asarray(ref[2]))
    # the blocks-per-call histogram rides the occupancy gauges
    occ = eng.occupancy()
    assert occ["observability/kv_spool_blocks_per_call_p50"] == 3.0
    assert occ["observability/kv_restore_blocks_per_call_p50"] == 3.0
