"""Prefix/KV-cache reuse tests: ref-counted allocator semantics, the radix
tree (insert / longest-match / LRU evict-under-pressure), copy-on-write
forking, engine-level token-exact parity of cached vs uncached runs (greedy
AND the (seed, position)-keyed stochastic sampler), preempt->resume over
shared blocks, and the shared-aware ragged-metadata validator.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference.v2 import (InferenceEngineV2,
                                        RaggedInferenceEngineConfig)
from deepspeed_tpu.inference.v2.model_implementations import RaggedLlama
from deepspeed_tpu.inference.v2.ragged import (BlockedAllocator,
                                               RadixPrefixCache)
from deepspeed_tpu.inference.v2.ragged.ragged_wrapper import (
    RaggedMetadataError, validate_ragged_metadata)
from deepspeed_tpu.inference.v2.ragged.sequence_descriptor import (
    DSSequenceDescriptor)
from deepspeed_tpu.models import LlamaConfig, LlamaForCausalLM
from deepspeed_tpu.serving import (ContinuousBatchScheduler, RequestState,
                                   SamplingParams, sample_one)

CFG = LlamaConfig.tiny(dtype=jnp.float32)


@pytest.fixture(scope="module")
def params():
    return LlamaForCausalLM(CFG).init(
        jax.random.key(0), np.zeros((1, 4), np.int32))["params"]


def _engine(params, token_budget=32, block_size=8, max_context=64,
            max_seqs=4, num_blocks=None, prefix_cache=True):
    cfg = RaggedInferenceEngineConfig.from_dict({
        "state_manager": {"max_ragged_batch_size": token_budget,
                          "max_ragged_sequence_count": max_seqs,
                          "max_context": max_context},
        "kv_cache": {"block_size": block_size,
                     "enable_prefix_cache": prefix_cache,
                     **({"num_blocks": num_blocks}
                        if num_blocks is not None else {})},
    })
    return InferenceEngineV2(RaggedLlama(CFG, block_size), params, cfg)


# --------------------------------------------------------------------- #
# Allocator refcounts (satellite: acquire/release + double-free compose)
# --------------------------------------------------------------------- #
def test_allocator_acquire_release_refcounts():
    a = BlockedAllocator(8)
    (b,) = a.allocate(1)
    assert a.refcount(b) == 1
    a.acquire([b])
    a.acquire([b])
    assert a.refcount(b) == 3
    a.free([b])                       # shared: decrements, never poisons
    assert a.refcount(b) == 2 and a.free_blocks == 6
    a.release([b])                    # release is the same refcounted drop
    assert a.refcount(b) == 1 and a.free_blocks == 6
    a.free([b])                       # last ref -> back on the free list
    assert a.refcount(b) == 0 and a.free_blocks == 7
    with pytest.raises(ValueError, match="double free"):
        a.free([b])


def test_allocator_acquire_errors():
    a = BlockedAllocator(8)
    with pytest.raises(ValueError, match="free block"):
        a.acquire([3])                # never allocated
    (b,) = a.allocate(1)
    a.free([b])
    with pytest.raises(ValueError, match="free block"):
        a.acquire([b])                # content already gone
    with pytest.raises(ValueError, match="trash"):
        a.acquire([0])
    with pytest.raises(ValueError, match="invalid block id"):
        a.acquire([99])


def test_allocator_shared_free_stays_atomic():
    """A rejected free() must not leak partial refcount drops, and
    over-release within ONE call is caught up front."""
    a = BlockedAllocator(8)
    x, y = a.allocate(2)
    a.acquire([x])                    # x at rc 2
    with pytest.raises(ValueError, match="double free"):
        a.free([x, x, x])             # 3 drops > 2 refs, atomic reject
    assert a.refcount(x) == 2 and a.refcount(y) == 1
    a.free([x, x, y])                 # exactly the refs held: all freed
    assert a.free_blocks == 7
    assert a._free_set == set(a._free) and len(a._free) == 7


def test_allocator_double_free_guard_composes_with_sharing():
    """The PR-2 companion-set double-free check still fires for truly
    free blocks while shared frees pass through as decrements."""
    a = BlockedAllocator(8)
    got = a.allocate(3)
    a.acquire(got[:1])
    a.free(got)                       # got[0] -> rc 1, others freed
    assert a.refcount(got[0]) == 1
    with pytest.raises(ValueError, match="double free"):
        a.free(got[1:2])              # already free
    a.free(got[:1])
    assert a.free_blocks == 7


# --------------------------------------------------------------------- #
# Radix tree mechanics
# --------------------------------------------------------------------- #
def _tree(num_blocks=32, bs=4):
    a = BlockedAllocator(num_blocks)
    return a, RadixPrefixCache(a, bs)


def test_radix_insert_and_longest_match():
    a, t = _tree()
    toks = list(range(10))            # 2 full blocks + tail of 2
    blocks = a.allocate(3)
    n, div = t.insert(toks, blocks)
    assert (n, div) == (2, False)     # only full blocks registered
    assert t.cached_blocks == 2
    assert t.match_blocks(toks, touch=False) == blocks[:2]
    assert t.match_len(toks) == 8
    assert t.match_len(toks[:6]) == 4          # prefix of a prefix
    assert t.match_len([9, 9, 9, 9, 9]) == 0   # diverges at block 0
    # divergent second block
    other = toks[:4] + [77, 77, 77, 77]
    assert t.match_len(other) == 4
    # tree refs: one per cached block
    assert a.refcount(blocks[0]) == 2 and a.refcount(blocks[1]) == 2
    assert a.refcount(blocks[2]) == 1          # tail block not cached


def test_radix_insert_divergence_keeps_existing():
    a, t = _tree()
    toks = list(range(8))
    b1 = a.allocate(2)
    t.insert(toks, b1)
    b2 = a.allocate(2)
    n, div = t.insert(toks, b2)        # same content, different blocks
    assert (n, div) == (0, True)
    assert t.match_blocks(toks, touch=False) == b1
    assert a.refcount(b2[0]) == 1      # caller's twin stayed private


def test_radix_lru_eviction_order_and_liveness():
    a, t = _tree()
    p1, p2 = [1] * 8, [2] * 8
    b1, b2 = a.allocate(2), a.allocate(2)
    t.insert(p1, b1)
    t.insert(p2, b2)
    a.free(b1)                         # "sequences" flushed: tree-only refs
    a.free(b2)
    t.match_blocks(p1)                 # p1 is now most-recently used
    # p2's chain is colder -> evicted first, leaf-first
    assert t.evict(2) == 2
    assert t.match_len(p2) == 0 and t.match_len(p1) == 8
    assert a.refcount(b2[0]) == 0 and a.refcount(b2[1]) == 0
    # blocks a live sequence still references are never evicted
    a.acquire(b1)                      # a "sequence" attaches
    assert t.evictable_blocks == 0
    assert t.evict(2) == 0
    assert t.match_len(p1) == 8
    a.free(b1)
    assert t.evictable_blocks == 2
    assert t.evict(99) == 2
    assert t.cached_blocks == 0
    assert a.free_blocks == 31


def test_evictable_count_tracks_refcount_transitions():
    """`evictable_blocks` is an O(1) allocator-maintained counter; it must
    stay in lockstep with refcount transitions from attach/flush/evict."""
    a, t = _tree()
    toks = list(range(8))
    blocks = a.allocate(2)
    t.insert(toks, blocks)             # seq + tree refs: rc 2, none evictable
    assert t.evictable_blocks == 0
    a.free(blocks)                     # seq flushed: tree-only, both evictable
    assert t.evictable_blocks == 2
    a.acquire(blocks[:1])              # a new seq attaches to block 0
    assert t.evictable_blocks == 1
    a.free(blocks[:1])
    assert t.evictable_blocks == 2
    assert t.evict(1) == 1             # leaf evicted, counter follows
    assert t.evictable_blocks == 1
    assert t.clear() == 1
    assert t.evictable_blocks == 0


def test_evict_heap_bounded_without_pressure():
    """Repeated warm attach/flush cycles with no eviction must not grow
    the candidate heap: one live entry per evictable node, not one per
    refcount 2->1 transition (a lifetime-proportional host leak)."""
    a, t = _tree()
    toks = list(range(8))
    blocks = a.allocate(2)
    t.insert(toks, blocks)
    a.free(blocks)                     # original "sequence" flushed
    for _ in range(100):               # 100 attach/flush cycles, no evict()
        a.acquire(blocks)
        a.free(blocks)
    assert len(t._evict_heap) <= t.cached_blocks
    # entries are still live: eviction under pressure works as before
    assert t.evict(2) == 2
    assert t.cached_blocks == 0 and not t._evict_heap


def test_radix_clear_releases_everything():
    a, t = _tree()
    toks = list(range(12))
    blocks = a.allocate(3)
    t.insert(toks, blocks)
    assert t.clear() == 3
    assert t.cached_blocks == 0 and t.match_len(toks) == 0
    a.free(blocks)                     # owner's own refs still intact
    assert a.free_blocks == 31


# --------------------------------------------------------------------- #
# State-manager attach: trim, COW fork, eviction pressure
# --------------------------------------------------------------------- #
def test_attach_prefix_trims_and_counts(params):
    eng = _engine(params)
    sm = eng.state_manager
    rng = np.random.default_rng(0)
    prompt = rng.integers(0, CFG.vocab_size, size=(20,)).tolist()
    eng.put([1], [prompt])
    eng.flush([1])
    assert sm.prefix_cache.cached_blocks == 2          # 16 of 20 tokens
    cached = eng.attach_prefix(2, prompt)
    assert cached == 16
    seq = sm.get_sequence(2)
    assert seq.seen_tokens == 16 and seq.shared_blocks == 2
    assert sm.prefix_cache.stats.hit_tokens == 16
    eng.put([2], [prompt[16:]])
    eng.flush([2])


def test_attach_fully_cached_prompt_cow_forks(params):
    """A prompt fully covered by warm blocks must still run its final
    token — the last block is copy-on-write forked so the (identical)
    rewrite never lands in a shared block."""
    eng = _engine(params)
    sm = eng.state_manager
    rng = np.random.default_rng(1)
    prompt = rng.integers(0, CFG.vocab_size, size=(16,)).tolist()
    l_cold = eng.put([1], [prompt])
    eng.flush([1])
    free_before = sm.allocator.free_blocks
    l_warm = eng.put([2], [prompt])
    seq = sm.get_sequence(2)
    assert sm.prefix_cache.stats.cow_forks == 1
    assert seq.seen_tokens == 16 and seq.shared_blocks == 1
    # forked block is private and distinct from the cached one
    cached_blocks = sm.prefix_cache.match_blocks(prompt, touch=False)
    assert seq.blocks[0] == cached_blocks[0]
    assert seq.blocks[1] != cached_blocks[1]
    np.testing.assert_array_equal(np.argmax(l_cold[1]), np.argmax(l_warm[2]))
    eng.flush([2])
    assert sm.allocator.free_blocks == free_before


def test_attach_single_token_prompt_never_attaches(params):
    eng = _engine(params)
    rng = np.random.default_rng(2)
    p = rng.integers(0, CFG.vocab_size, size=(9,)).tolist()
    eng.put([1], [p])
    eng.flush([1])
    assert eng.attach_prefix(2, p[:1]) == 0


def test_eviction_under_kv_pressure_through_engine(params):
    """With the pool nearly full of warm cache blocks, a new unrelated
    prefill must evict cold entries instead of failing — but never
    blocks a LIVE sequence still references."""
    eng = _engine(params, num_blocks=7, block_size=8)   # 6 usable
    sm = eng.state_manager
    rng = np.random.default_rng(3)
    a = rng.integers(0, CFG.vocab_size, size=(24,)).tolist()
    b = rng.integers(0, CFG.vocab_size, size=(24,)).tolist()
    eng.put([1], [a])
    eng.flush([1])
    assert sm.prefix_cache.cached_blocks == 3
    assert sm.allocator.free_blocks == 3
    assert sm.free_blocks == 6                 # 3 free + 3 evictable
    eng.put([2], [b])                          # 3 fresh: free list empty
    eng.put([3], [rng.integers(0, CFG.vocab_size,
                               size=(24,)).tolist()])  # forces eviction
    assert sm.prefix_cache.stats.evicted_blocks == 3   # a's cold chain
    # b's blocks were live (tree + sequence refs) and survived
    assert sm.prefix_cache.match_len(b) == 24
    eng.flush([2, 3])


def test_cow_fork_exhaustion_trims_instead_of_crashing(params):
    """When the only 'evictable' blocks ARE the matched prefix (the pool
    is exactly the warm chain), a fully cached prompt cannot COW-fork —
    attach must trim the final block and re-run it, not raise."""
    eng = _engine(params, num_blocks=3, block_size=8)   # 2 usable blocks
    sm = eng.state_manager
    rng = np.random.default_rng(11)
    prompt = rng.integers(0, CFG.vocab_size, size=(16,)).tolist()
    l_cold = eng.put([1], [prompt])
    eng.flush([1])
    assert sm.allocator.free_blocks == 0
    assert sm.prefix_cache.cached_blocks == 2
    l_warm = eng.put([2], [prompt])                     # must not raise
    assert sm.prefix_cache.stats.cow_forks == 0         # fork was impossible
    assert sm.prefix_cache.stats.hit_tokens == 8        # trimmed to 1 warm block
    np.testing.assert_array_equal(np.argmax(l_cold[1]), np.argmax(l_warm[2]))
    eng.flush([2])


def test_flush_keeps_cache_warm_and_free_blocks_truthful(params):
    eng = _engine(params)
    sm = eng.state_manager
    total = sm.allocator.num_blocks - 1
    rng = np.random.default_rng(4)
    p = rng.integers(0, CFG.vocab_size, size=(24,)).tolist()
    eng.put([1], [p])
    eng.flush([1])
    # allocator view shrank, schedulable view did not
    assert sm.allocator.free_blocks == total - 3
    assert sm.free_blocks == total
    assert sm.prefix_cache.evictable_blocks == 3


# --------------------------------------------------------------------- #
# Engine parity: cached run == uncached run, greedy and stochastic
# --------------------------------------------------------------------- #
def _greedy_chain(eng, uid, prompt, n_new):
    logits = eng.put([uid], [list(prompt)])
    toks = [int(np.argmax(logits[uid]))]
    for _ in range(n_new - 1):
        logits = eng.put([uid], [[toks[-1]]])
        toks.append(int(np.argmax(logits[uid])))
    eng.flush([uid])
    return toks


def test_cached_prefill_token_exact_vs_uncached(params):
    rng = np.random.default_rng(5)
    prompt = rng.integers(0, CFG.vocab_size, size=(21,)).tolist()
    ref = _greedy_chain(_engine(params, prefix_cache=False), 9, prompt, 6)
    eng = _engine(params)
    cold = _greedy_chain(eng, 1, prompt, 6)
    warm = _greedy_chain(eng, 2, prompt, 6)
    assert cold == ref and warm == ref
    assert eng.state_manager.prefix_cache.stats.hits == 1


def test_cached_prefill_reproducible_stochastic_sampling(params):
    """The (seed, uid, position)-keyed sampler must draw the SAME tokens
    from a cache-hit prefill as from a cold one — the logits are
    bit-identical (same blocks), so the draws are too."""
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

    eng = _engine(params)
    cold = chain(eng, 1)
    warm = chain(eng, 2)
    assert eng.state_manager.prefix_cache.stats.hits == 1
    assert cold == warm


def test_generated_tokens_register_into_tree(params):
    """Full blocks of GENERATED tokens are cached too: a resume/replay of
    prompt+generated hits past the prompt boundary."""
    eng = _engine(params, block_size=4)
    rng = np.random.default_rng(7)
    prompt = rng.integers(0, CFG.vocab_size, size=(8,)).tolist()
    toks = _greedy_chain(eng, 1, prompt, 8)
    hist = prompt + toks
    # prompt (2 blocks) + generated up to the last full block boundary
    assert eng.state_manager.prefix_cache.match_len(hist) >= 12


# --------------------------------------------------------------------- #
# Scheduler interop: preempt -> resume over shared blocks
# --------------------------------------------------------------------- #
def test_preempt_resume_with_shared_prefix_parity(params):
    """KV-pressure preemption with prefix caching ON: resumes re-attach
    to their own still-warm history blocks (recompute skipped) and stay
    token-for-token exact vs an uncached, unscheduled run."""
    rng = np.random.default_rng(8)
    shared = rng.integers(0, CFG.vocab_size, size=(8,)).tolist()
    # (8 new tokens: a scheduler that prepares the next batch under the
    # one in flight admits a tick later, and 6 no longer fill the pool)
    n_req, n_new = 6, 8
    prompts = [shared + rng.integers(0, CFG.vocab_size,
                                     size=(int(n),)).tolist()
               for n in rng.integers(2, 8, size=n_req)]
    ref_eng = _engine(params, token_budget=64, prefix_cache=False)
    want = [_greedy_chain(ref_eng, 500 + i, p, n_new)
            for i, p in enumerate(prompts)]

    # 5 usable blocks against 4-way concurrency at 2 private blocks each
    # (the shared-prompt block is deduped): preemption MUST occur
    eng = _engine(params, token_budget=32, block_size=8, max_context=48,
                  max_seqs=4, num_blocks=6)
    sched = ContinuousBatchScheduler(eng)
    reqs = []
    tick = 0
    while len(reqs) < n_req or sched.num_pending:
        if len(reqs) < n_req and tick % 2 == 0:
            reqs.append(sched.submit(
                prompts[len(reqs)],
                sampling=SamplingParams(max_new_tokens=n_new)))
        sched.step()
        tick += 1
        assert tick < 2000, "scheduler failed to converge"

    assert sched.metrics.preemptions >= 1
    for r, w in zip(reqs, want):
        assert r.state is RequestState.FINISHED, (r.uid, r.finish_reason)
        assert r.generated == w, \
            f"request {r.uid} (preempted {r.preemptions}x) diverged"
    # a preempted request's resume must have hit its own warm history
    stats = eng.state_manager.prefix_cache.stats
    assert stats.hits >= 1 and stats.hit_tokens > 0
    # teardown accounting: every non-cache block back on the free list
    sm = eng.state_manager
    assert sm.n_tracked_sequences == 0
    assert sm.free_blocks == sm.allocator.num_blocks - 1


def test_scheduler_admission_attaches_cached_prefix(params):
    """The scheduler's SplitFuse packing must start PAST the cached span:
    the engine never re-prefills warm tokens."""
    eng = _engine(params, token_budget=16)
    sched = ContinuousBatchScheduler(eng)
    rng = np.random.default_rng(9)
    prompt = rng.integers(0, CFG.vocab_size, size=(20,)).tolist()
    r1 = sched.submit(prompt, sampling=SamplingParams(max_new_tokens=2))
    sched.run_until_idle()

    calls = []
    orig, orig_prepare = eng.put, eng.prepare

    def spy(uids, tokens, **how):
        calls.append([len(t) for t in tokens])
        return orig(uids, tokens, **how)

    def prepare(uids, tokens=None, late=()):
        if tokens is not None:      # the scheduler's own greedy batch
            calls.append([len(t) for t in tokens])
        return orig_prepare(uids, tokens, late)

    eng.put, eng.prepare = spy, prepare
    r2 = sched.submit(prompt, sampling=SamplingParams(max_new_tokens=2))
    sched.run_until_idle()
    assert r2.generated == r1.generated
    # 16 of 20 prompt tokens cached -> the ONLY prefill chunk is 4 (the
    # 16-token budget would otherwise need two chunks)
    assert calls[0] == [4], calls
    assert eng.state_manager.prefix_cache.stats.hit_tokens >= 16


def test_scheduler_attach_cannot_overcommit_packed_chunks(params):
    """A cold chunk validated while warm blocks counted as evictable must
    not be invalidated by a LATER admission's attach pinning those blocks
    — the scheduler re-checks the packed set and defers the attacher
    instead of letting engine.put raise 'KV cache exhausted'."""
    eng = _engine(params, token_budget=64, max_context=96, num_blocks=14)
    sched = ContinuousBatchScheduler(eng)
    rng = np.random.default_rng(10)
    warm_prompt = rng.integers(0, CFG.vocab_size, size=(64,)).tolist()
    w = sched.submit(warm_prompt, sampling=SamplingParams(max_new_tokens=2))
    sched.run_until_idle()
    assert eng.state_manager.prefix_cache.cached_blocks == 8   # 5 free left

    cold_prompt = rng.integers(0, CFG.vocab_size, size=(41,)).tolist()
    a = sched.submit(cold_prompt, sampling=SamplingParams(max_new_tokens=2))
    b = sched.submit(warm_prompt, sampling=SamplingParams(max_new_tokens=2))
    sched.run_until_idle()            # must not raise KV-exhausted
    assert a.state is RequestState.FINISHED
    assert b.state is RequestState.FINISHED
    assert b.generated == w.generated
    # the deferral is a preemption: request + metrics both record it
    assert b.preemptions >= 1
    assert sched.metrics.preemptions >= 1
    # discarded attaches roll their stats back — only b's final successful
    # attach counts as a hit (w and a are cold misses), so the saved-token
    # accounting never includes a prefill skip that was flushed unused
    stats = eng.state_manager.prefix_cache.stats
    assert stats.hits == 1, stats.as_dict()
    assert 0 < stats.hit_tokens <= 63


# --------------------------------------------------------------------- #
# Shared-aware ragged metadata validation
# --------------------------------------------------------------------- #
def _seq(uid, seen, blocks, shared=0):
    s = DSSequenceDescriptor(uid=uid, seen_tokens=seen, blocks=list(blocks))
    s.shared_blocks = shared
    return s


def test_validate_metadata_allows_mutually_shared_blocks():
    a = _seq(1, 8, [3, 4], shared=1)
    b = _seq(2, 8, [3, 5], shared=1)
    validate_ragged_metadata([a, b], [np.empty(1), np.empty(1)], 8)


def test_validate_metadata_rejects_one_sided_alias():
    a = _seq(1, 8, [3, 4], shared=1)
    b = _seq(2, 8, [5, 3], shared=1)       # 3 is PRIVATE in b's table
    with pytest.raises(RaggedMetadataError, match="outside their shared"):
        validate_ragged_metadata([a, b], [np.empty(1), np.empty(1)], 8)


def test_validate_metadata_rejects_write_into_shared_prefix():
    s = _seq(1, 4, [3, 4], shared=1)       # write at pos 4 < 1*8
    with pytest.raises(RaggedMetadataError, match="copy-on-write"):
        validate_ragged_metadata([s], [np.empty(1)], 8)


def test_validate_metadata_still_rejects_plain_alias_and_dupes():
    a = _seq(1, 8, [3, 4], shared=0)
    b = _seq(2, 8, [3, 5], shared=0)
    with pytest.raises(RaggedMetadataError, match="aliased"):
        validate_ragged_metadata([a, b], [np.empty(1), np.empty(1)], 8)
    c = _seq(3, 16, [4, 4], shared=2)
    with pytest.raises(RaggedMetadataError, match="listed twice"):
        validate_ragged_metadata([c], [np.empty(0)], 8)
