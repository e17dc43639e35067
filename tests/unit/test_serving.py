"""Serving-layer tests: request lifecycle, batched sampling, SplitFuse
packing/admission boundaries, KV-pressure preemption with recompute-resume
parity, termination, allocator hardening, metrics/monitor plumbing, and
the 30-second smoke tool.

Reference pattern: tests/unit/inference/v2/ragged plus the MII batching
tests — correctness bar is token-for-token parity with an unscheduled
(one-request-at-a-time) greedy loop on the same engine params.
"""

import importlib.util
import pathlib
import time
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference.v2 import (InferenceEngineV2,
                                        RaggedInferenceEngineConfig)
from deepspeed_tpu.inference.v2.model_implementations import RaggedLlama
from deepspeed_tpu.inference.v2.ragged import BlockedAllocator
from deepspeed_tpu.models import LlamaConfig, LlamaForCausalLM
from deepspeed_tpu.serving import (ContinuousBatchScheduler, QueueFullError,
                                   Request, RequestState, SamplingParams,
                                   sample_batch)

CFG = LlamaConfig.tiny(dtype=jnp.float32)


@pytest.fixture(scope="module")
def params():
    return LlamaForCausalLM(CFG).init(
        jax.random.key(0), np.zeros((1, 4), np.int32))["params"]


def _engine(params, token_budget=32, block_size=8, max_context=64,
            max_seqs=4, num_blocks=None):
    cfg = RaggedInferenceEngineConfig.from_dict({
        "state_manager": {"max_ragged_batch_size": token_budget,
                          "max_ragged_sequence_count": max_seqs,
                          "max_context": max_context},
        "kv_cache": {"block_size": block_size,
                     **({"num_blocks": num_blocks}
                        if num_blocks is not None else {})},
    })
    return InferenceEngineV2(RaggedLlama(CFG, block_size), params, cfg)


def _greedy_reference(params, prompts, n_new):
    """Unscheduled one-at-a-time greedy loop (put + host argmax) — the
    token-for-token bar every scheduler run must meet."""
    eng = _engine(params, token_budget=64, max_context=64)
    outs = []
    for i, p in enumerate(prompts):
        uid = 500 + i
        logits = eng.put([uid], [list(p)])
        tok = int(np.argmax(logits[uid]))
        toks = [tok]
        for _ in range(n_new - 1):
            logits = eng.put([uid], [[tok]])
            tok = int(np.argmax(logits[uid]))
            toks.append(tok)
        eng.flush([uid])
        outs.append(toks)
    return outs


# --------------------------------------------------------------------- #
# Request lifecycle state machine
# --------------------------------------------------------------------- #
def test_request_state_machine():
    r = Request(uid=1, prompt=[1, 2, 3])
    assert r.state is RequestState.QUEUED
    r.transition(RequestState.PREFILL)
    r.transition(RequestState.DECODE)
    r.transition(RequestState.PREEMPTED)
    r.transition(RequestState.PREFILL)
    r.transition(RequestState.FINISHED)
    with pytest.raises(RuntimeError, match="illegal transition"):
        r.transition(RequestState.DECODE)
    with pytest.raises(RuntimeError, match="illegal transition"):
        Request(uid=2, prompt=[1]).transition(RequestState.DECODE)


def test_request_history_and_feed_accounting():
    r = Request(uid=1, prompt=[5, 6, 7])
    assert r.history == [5, 6, 7] and r.remaining_feed == 3
    r.fed = 3
    r.emit(9, now=1.0)
    assert r.history == [5, 6, 7, 9] and r.remaining_feed == 1
    assert r.first_token_time == 1.0


def test_request_streaming_callback():
    got = []
    r = Request(uid=1, prompt=[1],
                on_token=lambda req, tok: got.append((req.uid, tok)))
    r.emit(4, now=0.0)
    r.emit(5, now=0.1)
    assert got == [(1, 4), (1, 5)] and r.generated == [4, 5]


def test_raising_stream_callback_is_disabled_not_fatal(params):
    """A broken on_token handler must not corrupt the tick for other
    requests: the callback is disabled, generation completes."""
    rng = np.random.default_rng(15)
    prompts = [rng.integers(0, 256, size=(5,)).tolist() for _ in range(2)]
    calls = []

    def bad(req, tok):
        calls.append(tok)
        raise RuntimeError("client went away")

    sched = ContinuousBatchScheduler(_engine(params))
    r_bad = sched.submit(prompts[0], sampling=SamplingParams(max_new_tokens=4),
                         on_token=bad)
    r_ok = sched.submit(prompts[1], sampling=SamplingParams(max_new_tokens=4))
    sched.run_until_idle()
    assert r_bad.state is RequestState.FINISHED
    assert r_ok.state is RequestState.FINISHED
    assert len(r_bad.generated) == 4 and len(r_ok.generated) == 4
    assert calls == r_bad.generated[:1]       # disabled after first raise
    assert r_bad.on_token is None


def test_sampling_params_validation():
    with pytest.raises(ValueError):
        SamplingParams(max_new_tokens=0)
    with pytest.raises(ValueError):
        SamplingParams(greedy=False, temperature=0.0)
    with pytest.raises(ValueError):
        SamplingParams(top_k=-1)
    assert SamplingParams(eos_token_id=3).is_stop_token(3)
    assert SamplingParams(stop_token_ids=(7,)).is_stop_token(7)
    assert not SamplingParams().is_stop_token(7)


# --------------------------------------------------------------------- #
# Batched sampling
# --------------------------------------------------------------------- #
def test_sample_batch_greedy_is_argmax():
    rng = np.random.default_rng(0)
    logits = rng.normal(size=(5, 32)).astype(np.float32)
    toks = sample_batch(logits, [SamplingParams()] * 5, [0] * 5,
                        list(range(5)))
    np.testing.assert_array_equal(toks, np.argmax(logits, axis=-1))


def test_sample_batch_topk_support_and_determinism():
    rng = np.random.default_rng(1)
    logits = rng.normal(size=(4, 64)).astype(np.float32)
    sp = [SamplingParams(greedy=False, temperature=0.8, top_k=4, seed=s)
          for s in range(4)]
    toks = sample_batch(logits, sp, [3] * 4, [10, 11, 12, 13])
    for i in range(4):
        top4 = set(np.argsort(logits[i])[-4:].tolist())
        assert int(toks[i]) in top4
    # same (seed, uid, position) -> same draw, regardless of batch
    # composition (the preempt/resume reproducibility contract)
    again = sample_batch(logits[1:2], sp[1:2], [3], [11])
    assert int(again[0]) == int(toks[1])
    # a different position draws from a fresh stream
    moved = sample_batch(np.tile(logits[1:2], (64, 1)), [sp[1]] * 64,
                         list(range(64)), [11] * 64)
    assert len(set(moved.tolist())) > 1


def test_sample_batch_shared_seed_requests_draw_independently():
    """Concurrent requests sharing one SamplingParams (and its seed) must
    NOT produce identical streams — the uid is part of the noise key."""
    rng = np.random.default_rng(14)
    row = rng.normal(size=(1, 256)).astype(np.float32)
    sp = SamplingParams(greedy=False, temperature=1.0, top_k=0, seed=0)
    # same logits, same seed, same positions, different uids
    toks = sample_batch(np.tile(row, (32, 1)), [sp] * 32, [0] * 32,
                        list(range(32)))
    assert len(set(toks.tolist())) > 1


def test_sample_batch_mixed_greedy_and_stochastic():
    rng = np.random.default_rng(2)
    logits = rng.normal(size=(3, 16)).astype(np.float32)
    sp = [SamplingParams(),
          SamplingParams(greedy=False, temperature=0.5, top_k=2, seed=9),
          SamplingParams()]
    toks = sample_batch(logits, sp, [0, 0, 0], [1, 2, 3])
    assert toks[0] == np.argmax(logits[0])
    assert toks[2] == np.argmax(logits[2])
    assert int(toks[1]) in set(np.argsort(logits[1])[-2:].tolist())


# --------------------------------------------------------------------- #
# Scheduler: completion + parity with the unscheduled loop
# --------------------------------------------------------------------- #
def test_scheduler_matches_unscheduled_greedy(params):
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, CFG.vocab_size, size=(n,)).tolist()
               for n in (5, 11, 3)]
    want = _greedy_reference(params, prompts, n_new=6)

    sched = ContinuousBatchScheduler(_engine(params, token_budget=8))
    # budget 8 < sum of prompts -> SplitFuse chunking across ticks
    reqs = [sched.submit(p, sampling=SamplingParams(max_new_tokens=6))
            for p in prompts]
    sched.run_until_idle()
    for r, w in zip(reqs, want):
        assert r.state is RequestState.FINISHED
        assert r.finish_reason == "length"
        assert r.generated == w


def test_scheduler_streaming_and_slo_fields(params):
    rng = np.random.default_rng(4)
    prompt = rng.integers(0, CFG.vocab_size, size=(6,)).tolist()
    streamed = []
    sched = ContinuousBatchScheduler(_engine(params))
    r = sched.submit(prompt, sampling=SamplingParams(max_new_tokens=5),
                     on_token=lambda req, t: streamed.append(t))
    sched.run_until_idle()
    assert streamed == r.generated and len(streamed) == 5
    assert r.ttft is not None and r.ttft >= 0
    assert r.queue_wait is not None and r.queue_wait >= 0
    assert r.tpot is not None and r.tpot >= 0
    assert r.finish_time is not None


# --------------------------------------------------------------------- #
# Admission boundaries: exact token budget / max_seqs
# --------------------------------------------------------------------- #
def _spy_put(engine):
    """Record the chunk lengths of every ragged forward the scheduler
    asks for: a ``put``, or a ``prepare`` that is handed tokens (``put``
    itself prepares from what is pending)."""
    calls = []
    orig, orig_prepare = engine.put, engine.prepare

    def spy(uids, tokens, **how):
        calls.append([len(t) for t in tokens])
        return orig(uids, tokens, **how)

    def prepare(uids, tokens=None, late=()):
        if tokens is not None:
            calls.append([len(t) for t in tokens])
        return orig_prepare(uids, tokens, late)

    engine.put, engine.prepare = spy, prepare
    return calls


def test_admission_exact_token_budget(params):
    eng = _engine(params, token_budget=16, max_context=32)
    calls = _spy_put(eng)
    sched = ContinuousBatchScheduler(eng)
    rng = np.random.default_rng(5)
    # two 8-token prompts pack ONE forward at exactly the budget
    reqs = [sched.submit(rng.integers(0, 256, size=(8,)).tolist(),
                         sampling=SamplingParams(max_new_tokens=2))
            for _ in range(2)]
    sched.step()
    assert calls[0] == [8, 8]
    sched.run_until_idle()
    assert all(r.state is RequestState.FINISHED for r in reqs)
    # a 17-token prompt must split 16 + 1 across ticks
    calls.clear()
    r = sched.submit(rng.integers(0, 256, size=(17,)).tolist(),
                     sampling=SamplingParams(max_new_tokens=2))
    sched.run_until_idle()
    assert r.state is RequestState.FINISHED
    assert calls[0] == [16] and calls[1][0] == 1
    assert all(sum(c) <= 16 for c in calls)


def test_admission_max_seqs_boundary(params):
    sched = ContinuousBatchScheduler(
        _engine(params, token_budget=64, max_seqs=2, max_context=32))
    rng = np.random.default_rng(6)
    reqs = [sched.submit(rng.integers(0, 256, size=(4,)).tolist(),
                         sampling=SamplingParams(max_new_tokens=4))
            for _ in range(5)]
    while sched.num_pending:
        sched.step()
        assert len(sched.running_uids) <= 2
    assert all(r.state is RequestState.FINISHED for r in reqs)
    assert all(len(r.generated) == 4 for r in reqs)


def test_submit_rejections(params):
    sched = ContinuousBatchScheduler(
        _engine(params, max_context=32, num_blocks=3))
    with pytest.raises(ValueError, match="max_context"):
        sched.submit(list(range(32)))
    # 2 usable blocks of 8 tokens; a 16-token prompt needs 3
    with pytest.raises(ValueError, match="KV blocks"):
        sched.submit([1] * 16)
    with pytest.raises(ValueError, match="empty prompt"):
        sched.submit([])
    r = sched.submit([1, 2, 3])
    with pytest.raises(ValueError, match="already"):
        sched.submit([4, 5], uid=r.uid)
    with pytest.raises(ValueError, match="deadline_s"):
        sched.submit([1, 2], deadline_s=-1.0)


def test_bounded_admission_queue_rejects_overload(params):
    sched = ContinuousBatchScheduler(_engine(params), max_queue=2)
    sched.submit([1, 2], sampling=SamplingParams(max_new_tokens=1))
    sched.submit([3, 4], sampling=SamplingParams(max_new_tokens=1))
    with pytest.raises(QueueFullError, match="max_queue=2"):
        sched.submit([5, 6], sampling=SamplingParams(max_new_tokens=1))
    assert sched.metrics.snapshot()["rejected"] == 1
    sched.step()  # admits the queued pair -> admission reopens
    r3 = sched.submit([5, 6], sampling=SamplingParams(max_new_tokens=1))
    sched.run_until_idle(max_ticks=20)
    assert r3.state is RequestState.FINISHED
    with pytest.raises(ValueError, match="max_queue"):
        ContinuousBatchScheduler(_engine(params), max_queue=0)


def test_deadline_exceeded_fails_queued_request(params):
    sched = ContinuousBatchScheduler(_engine(params))
    ok = sched.submit([1, 2, 3], sampling=SamplingParams(max_new_tokens=2))
    doomed = sched.submit([4, 5, 6],
                          sampling=SamplingParams(max_new_tokens=64),
                          deadline_s=0.01)
    time.sleep(0.03)
    sched.run_until_idle(max_ticks=50)
    assert doomed.state is RequestState.FAILED
    assert doomed.finish_reason == "deadline"
    assert ok.state is RequestState.FINISHED
    snap = sched.metrics.snapshot()
    assert snap["deadline_exceeded"] == 1.0 and snap["failed"] == 1.0


def test_deadline_exceeded_fails_running_request_and_frees_kv(params):
    eng = _engine(params)
    sched = ContinuousBatchScheduler(eng)
    req = sched.submit(list(range(1, 9)),
                       sampling=SamplingParams(max_new_tokens=64),
                       deadline_s=0.05)
    sched.step()
    assert req.state in (RequestState.PREFILL, RequestState.DECODE)
    time.sleep(0.06)
    sched.step()
    assert req.state is RequestState.FAILED
    assert req.finish_reason == "deadline"
    assert req.generated  # tokens emitted before the SLO blew stay visible
    sm = eng.state_manager
    assert sm.n_tracked_sequences == 0  # device KV fully released
    assert sched.metrics.snapshot()["deadline_exceeded"] == 1.0


# --------------------------------------------------------------------- #
# KV exhaustion -> preempt -> resume: token-for-token greedy parity
# (acceptance: >= 8 Poisson-arrival requests, >= 1 forced preemption)
# --------------------------------------------------------------------- #
def test_preemption_resume_greedy_parity(params):
    rng = np.random.default_rng(7)
    n_req, n_new = 8, 8
    prompts = [rng.integers(0, CFG.vocab_size, size=(int(n),)).tolist()
               for n in rng.integers(6, 16, size=n_req)]
    want = _greedy_reference(params, prompts, n_new)

    # 6 usable blocks of 8 tokens vs 8 requests needing up to 3 blocks
    # each: concurrency is KV-bound, so preemption MUST occur
    eng = _engine(params, token_budget=32, block_size=8, max_context=48,
                  max_seqs=4, num_blocks=7)
    sched = ContinuousBatchScheduler(eng)
    # Poisson arrivals measured in scheduler ticks (deterministic on CPU)
    arrival_tick = np.floor(np.cumsum(
        rng.exponential(1.2, size=n_req))).astype(int)
    reqs = []
    tick = 0
    while len(reqs) < n_req or sched.num_pending:
        while len(reqs) < n_req and arrival_tick[len(reqs)] <= tick:
            reqs.append(sched.submit(
                prompts[len(reqs)],
                sampling=SamplingParams(max_new_tokens=n_new)))
        sched.step()
        tick += 1
        assert tick < 2000, "scheduler failed to converge"

    assert sched.metrics.preemptions >= 1, \
        "KV was sized to force preemption but none happened"
    assert any(r.preemptions > 0 for r in reqs)
    for r, w in zip(reqs, want):
        assert r.state is RequestState.FINISHED, (r.uid, r.finish_reason)
        assert r.generated == w, \
            f"request {r.uid} (preempted {r.preemptions}x) diverged"
    # all KV released
    sm = eng.state_manager
    assert sm.n_tracked_sequences == 0
    assert sm.free_blocks == sm.allocator.num_blocks - 1


def test_backlog_tokens_incremental_counter_never_drifts(params):
    """backlog_tokens() keeps an incremental counter for parked requests
    (O(max_seqs) per probe — the router calls it every submit); it must
    agree with a brute-force walk through every submit / admit / preempt
    / resume / deadline-fail / finish transition."""
    def brute(s):
        return sum(s._work(r) for r in [*s._queued, *s._running.values(),
                                        *s._preempted])

    rng = np.random.default_rng(11)
    eng = _engine(params, token_budget=32, block_size=8, max_context=48,
                  max_seqs=4, num_blocks=7)   # KV-bound: forces preemption
    sched = ContinuousBatchScheduler(eng)
    reqs = []
    for i in range(8):
        prompt = rng.integers(0, CFG.vocab_size,
                              size=(int(rng.integers(6, 16)),)).tolist()
        reqs.append(sched.submit(
            prompt, sampling=SamplingParams(max_new_tokens=8),
            deadline_s=(1e-9 if i == 5 else None)))   # one deadline fail
        assert sched.backlog_tokens() == brute(sched)
    ticks = 0
    while sched.num_pending:
        sched.step()
        assert sched.backlog_tokens() == brute(sched)
        ticks += 1
        assert ticks < 2000, "scheduler failed to converge"
    assert sched.metrics.preemptions >= 1   # the interesting paths ran
    assert sched.backlog_tokens() == 0


def test_history_outgrowing_pool_truncates_not_livelocks(params):
    """A request whose history outgrows the ENTIRE KV pool must finish
    truncated (keeping its tokens), not spin in an infinite
    preempt -> recompute -> preempt cycle: 6 usable blocks hold 48
    tokens, so a 44-token prompt can only ever emit 5 tokens even
    though max_new_tokens asks for 12."""
    rng = np.random.default_rng(12)
    prompt = rng.integers(0, CFG.vocab_size, size=(44,)).tolist()
    want = _greedy_reference(params, [prompt], n_new=5)[0]

    eng = _engine(params, token_budget=32, block_size=8, max_context=56,
                  num_blocks=7)
    sched = ContinuousBatchScheduler(eng)
    r = sched.submit(prompt, sampling=SamplingParams(max_new_tokens=12))
    sched.run_until_idle(max_ticks=100)
    assert sched.num_pending == 0, "scheduler livelocked"
    assert r.state is RequestState.FINISHED
    assert r.finish_reason == "length"
    assert r.generated == want               # truncated, still greedy-exact
    sm = eng.state_manager
    assert sm.n_tracked_sequences == 0
    assert sm.free_blocks == sm.allocator.num_blocks - 1


def test_stall_with_multiple_runners_preempts_not_fails(params):
    """A joint mid-prefill KV deadlock is recoverable: _handle_stall must
    preempt the newest runner (freeing its blocks) rather than FAIL a
    request both of whose halves fit the pool individually."""
    eng = _engine(params, token_budget=16, block_size=8, max_context=48,
                  num_blocks=5)
    sched = ContinuousBatchScheduler(eng)
    rng = np.random.default_rng(13)
    reqs = []
    for uid in (1, 2):
        r = Request(uid=uid,
                    prompt=rng.integers(0, 256, size=(24,)).tolist())
        eng.put([uid], [r.prompt[:16]])      # mid-prefill, 2 blocks held
        r.transition(RequestState.PREFILL)
        r.fed, r.admitted_at = 16, uid
        sched._running[uid] = r
        reqs.append(r)
    assert eng.state_manager.free_blocks == 0    # jointly exhausted

    sched._handle_stall()
    a, b = reqs
    assert b.state is RequestState.PREEMPTED and b.fed == 0   # newest
    assert a.state is RequestState.PREFILL                    # untouched
    assert eng.state_manager.get_sequence(2) is None
    assert eng.state_manager.free_blocks == 2                 # blocks back
    assert sched.metrics.preemptions == 1

    # a SINGLE stalled holder can never fit — that one fails
    del sched._preempted[:]
    sched._handle_stall()
    assert a.state is RequestState.FAILED
    assert a.finish_reason == "kv_capacity"


def test_preemption_victim_is_lowest_priority_then_newest(params):
    sched = ContinuousBatchScheduler(_engine(params))
    a = Request(uid=1, prompt=[1], priority=5)
    b = Request(uid=2, prompt=[1], priority=0)
    c = Request(uid=3, prompt=[1], priority=0)
    for i, r in enumerate((a, b, c)):
        r.state = RequestState.DECODE
        r.admitted_at = i
        sched._running[r.uid] = r
    assert sched._pick_victim() is c      # lowest priority, newest
    del sched._running[3]
    assert sched._pick_victim() is b
    del sched._running[2]
    assert sched._pick_victim() is a


# --------------------------------------------------------------------- #
# Termination: stop tokens and max_new_tokens
# --------------------------------------------------------------------- #
def test_stop_token_termination(params):
    rng = np.random.default_rng(8)
    prompt = rng.integers(0, CFG.vocab_size, size=(6,)).tolist()
    ref = _greedy_reference(params, [prompt], n_new=8)[0]
    stop = ref[3]
    cut = ref.index(stop) + 1   # first occurrence ends the stream

    sched = ContinuousBatchScheduler(_engine(params))
    r = sched.submit(prompt, sampling=SamplingParams(
        max_new_tokens=8, stop_token_ids=(stop,)))
    sched.run_until_idle()
    assert r.state is RequestState.FINISHED
    assert r.finish_reason == "stop"
    assert r.generated == ref[:cut]        # stop token included

    # eos_token_id takes the same path
    sched2 = ContinuousBatchScheduler(_engine(params))
    r2 = sched2.submit(prompt, sampling=SamplingParams(
        max_new_tokens=8, eos_token_id=stop))
    sched2.run_until_idle()
    assert r2.finish_reason == "stop" and r2.generated == ref[:cut]


def test_max_new_tokens_termination(params):
    rng = np.random.default_rng(9)
    prompt = rng.integers(0, CFG.vocab_size, size=(4,)).tolist()
    sched = ContinuousBatchScheduler(_engine(params))
    r = sched.submit(prompt, sampling=SamplingParams(max_new_tokens=3))
    sched.run_until_idle()
    assert r.finish_reason == "length" and len(r.generated) == 3


# --------------------------------------------------------------------- #
# Engine preemption primitives: flush_to_host / resume
# --------------------------------------------------------------------- #
def test_engine_flush_to_host_resume_roundtrip(params):
    rng = np.random.default_rng(10)
    prompt = rng.integers(0, CFG.vocab_size, size=(6,)).tolist()
    want = _greedy_reference(params, [prompt], n_new=6)[0]

    eng = _engine(params)
    free0 = eng.state_manager.free_blocks
    logits = eng.put([1], [prompt])
    toks = [int(np.argmax(logits[1]))]
    for _ in range(2):
        logits = eng.put([1], [[toks[-1]]])
        toks.append(int(np.argmax(logits[1])))

    snap = eng.flush_to_host([1])
    assert snap[1]["seen_tokens"] == len(prompt) + 2
    assert eng.state_manager.free_blocks == free0   # blocks released
    assert eng.state_manager.get_sequence(1) is None

    # recompute-resume: re-prefill prompt + generated, continue greedy
    logits = eng.resume(1, prompt + toks)
    toks.append(int(np.argmax(logits[1])))
    for _ in range(2):
        logits = eng.put([1], [[toks[-1]]])
        toks.append(int(np.argmax(logits[1])))
    eng.flush([1])
    assert toks == want


def test_engine_flush_to_host_errors(params):
    eng = _engine(params)
    with pytest.raises(ValueError, match="unknown sequence"):
        eng.flush_to_host([99])
    eng.put([1], [[1, 2, 3]])
    with pytest.raises(RuntimeError, match="still live"):
        eng.resume(1, [1, 2, 3, 4])
    eng.flush([1])


# --------------------------------------------------------------------- #
# Allocator hardening (O(1) double-free checks, order preserved)
# --------------------------------------------------------------------- #
def test_allocator_exhaustion_and_errors():
    a = BlockedAllocator(8)
    got = a.allocate(7)
    with pytest.raises(RuntimeError, match="exhausted"):
        a.allocate(1)
    a.free(got)
    with pytest.raises(ValueError, match="trash"):
        a.free([0])
    with pytest.raises(ValueError, match="invalid block id"):
        a.free([8])
    with pytest.raises(ValueError, match="invalid block id"):
        a.free([-1])


def test_allocator_double_free_detected():
    a = BlockedAllocator(8)
    got = a.allocate(3)
    a.free(got[:1])
    with pytest.raises(ValueError, match="double free"):
        a.free(got[:1])
    with pytest.raises(ValueError, match="double free"):
        a.free([got[1], got[1]])      # duplicate within one call
    # a failed free() must not have corrupted state
    a.free(got[1:])
    assert a.free_blocks == 7


def test_allocator_list_set_stay_consistent():
    a = BlockedAllocator(16)
    order0 = list(a._free)
    x = a.allocate(5)
    y = a.allocate(3)
    a.free(x)
    a.free(y)
    assert sorted(a._free) == sorted(order0)
    assert a._free_set == set(a._free)
    assert len(a._free) == len(a._free_set)      # no duplicates
    # allocation order follows the list, not the set
    assert a.allocate(8) == (order0[8:] + x + y)[:8]


# --------------------------------------------------------------------- #
# Metrics + monitor plumbing (wall-clock x-axis)
# --------------------------------------------------------------------- #
def _csv_monitor(tmp_path):
    from deepspeed_tpu.monitor.monitor import MonitorMaster

    off = types.SimpleNamespace(enabled=False)
    cfg = types.SimpleNamespace(
        tensorboard=off, wandb=off,
        csv_monitor=types.SimpleNamespace(enabled=True,
                                          output_path=str(tmp_path),
                                          job_name="serve"))
    return MonitorMaster(cfg)


def test_serving_metrics_export_wallclock_csv(params, tmp_path):
    import csv

    mon = _csv_monitor(tmp_path)
    sched = ContinuousBatchScheduler(_engine(params), monitor=mon)
    rng = np.random.default_rng(11)
    for _ in range(2):
        sched.submit(rng.integers(0, 256, size=(5,)).tolist(),
                     sampling=SamplingParams(max_new_tokens=3))
    sched.run_until_idle()

    snap = sched.metrics.snapshot()
    assert snap["finished"] == 2 and snap["total_tokens"] == 6
    assert snap["p50_ttft_s"] > 0 and snap["p95_ttft_s"] >= snap["p50_ttft_s"]
    assert snap["goodput_tokens_per_s"] > 0

    f = tmp_path / "serve" / "serving_finished.csv"
    assert f.exists(), list((tmp_path / "serve").iterdir())
    rows = list(csv.reader(f.open()))
    assert rows[0] == ["step", "serving/finished"]
    # x is a wall-clock float (time.time()), not a fabricated int step
    x = float(rows[-1][0])
    assert x > 1e9 and not float(x).is_integer()
    assert float(rows[-1][1]) == 2.0


def test_serving_metrics_series_hold_one_window(monkeypatch):
    """``ttft_s``, ``tpot_s``, ``queue_wait_s`` and ``decode_tick_s`` keep
    the last ``window_s`` seconds of their own activity, not an entry a
    request (a decode tick) for the life of the server; the snapshot's
    names are what they were, and a series that has gone quiet keeps its
    last window."""
    from deepspeed_tpu.serving import metrics as metrics_mod
    from deepspeed_tpu.serving.request import Request, RequestState

    clock = [100.0]
    monkeypatch.setattr(metrics_mod.time, "monotonic", lambda: clock[0])
    m = metrics_mod.ServingMetrics(window_s=10.0)
    for i in range(50):                 # a request and a tick a second
        clock[0] = 100.0 + i
        req = Request(uid=i + 1, prompt=[1, 2], arrival_time=clock[0] - 1.0)
        req.first_scheduled_time = clock[0] - 0.9
        req.emit(7, clock[0] - 0.5)
        req.emit(8, clock[0] - 0.5 + 0.01 * (i + 1))
        req.state = RequestState.FINISHED
        m.record_finish(req)
        m.record_decode_tick(1, 1, 0.001 * (i + 1), clock[0])
    for series in (m.ttft_s, m.tpot_s, m.queue_wait_s, m.decode_tick_s):
        assert len(series) == 11        # now - 10 s .. now, both ends
    snap = m.snapshot()
    assert snap["finished"] == 50 and snap["decode_ticks"] == 50
    # the percentiles are those of the window: requests 40 .. 50
    assert snap["p50_tpot_s"] == pytest.approx(0.45)
    assert snap["p50_decode_tick_s"] == pytest.approx(0.045)
    assert snap["p50_ttft_s"] == pytest.approx(0.5)
    assert snap["p50_queue_wait_s"] == pytest.approx(0.1)
    clock[0] += 3600.0                  # an idle hour: the last window stays
    assert m.snapshot()["p50_tpot_s"] == pytest.approx(0.45)
    m.record_decode_tick(1, 1, 0.5, clock[0])
    assert m.decode_tick_s.values() == [0.5]


def test_monitor_int_steps_unchanged(tmp_path):
    import csv

    mon = _csv_monitor(tmp_path)
    mon.write_events([("Train/lr", 0.1, 7)])
    rows = list(csv.reader((tmp_path / "serve" / "Train_lr.csv").open()))
    assert rows[1] == ["7", "0.1"]


# --------------------------------------------------------------------- #
# Graceful shutdown: stop admission, drain, fail leftovers as "shutdown"
# --------------------------------------------------------------------- #
def test_shutdown_drain_completes(params):
    eng = _engine(params)
    sched = ContinuousBatchScheduler(eng)
    reqs = [sched.submit([1, 2, 3], sampling=SamplingParams(max_new_tokens=4)),
            sched.submit([4, 5], sampling=SamplingParams(max_new_tokens=4))]
    sched.step()                                  # in-flight work exists
    assert sched.shutdown(drain_deadline=60.0) is True
    for r in reqs:
        assert r.state is RequestState.FINISHED
        assert len(r.generated) == 4              # nothing truncated
    assert sched.metrics.shutdown_failed == 0
    assert sched.metrics.snapshot()["shutdown_failed"] == 0.0
    # admission is closed for good
    with pytest.raises(RuntimeError, match="shutting down"):
        sched.submit([7, 8])
    assert sched.metrics.rejected == 1


def test_shutdown_deadline_expires_fails_pending(params):
    eng = _engine(params)
    sched = ContinuousBatchScheduler(eng)
    running = sched.submit([1, 2, 3],
                           sampling=SamplingParams(max_new_tokens=8))
    queued = sched.submit([4, 5, 6],
                          sampling=SamplingParams(max_new_tokens=8))
    sched.step()
    assert sched.shutdown(drain_deadline=0.0) is False
    for r in (running, queued):
        assert r.state is RequestState.FAILED
        assert r.finish_reason == "shutdown"
    assert sched.metrics.shutdown_failed == 2
    assert sched.num_pending == 0
    # device KV fully released: a new scheduler could start on this engine
    sm = eng.state_manager
    assert sm.n_tracked_sequences == 0
    assert sm.free_blocks == sm.allocator.num_blocks - 1


# --------------------------------------------------------------------- #
# Device-resident decode tick (the put()-path host transfer killer)
# --------------------------------------------------------------------- #
def _spy_paths(engine):
    """Record which engine entry point each tick used."""
    paths = []
    orig_put, orig_ds = engine.put, engine.decode_step
    orig_prepare = engine.prepare

    def put(uids, tokens, **how):
        paths.append(("put", [len(t) for t in tokens]))
        return orig_put(uids, tokens, **how)

    def prepare(uids, tokens=None, late=()):
        if tokens is not None:      # a ragged forward, in two halves
            paths.append(("put", [len(t) for t in tokens]))
        return orig_prepare(uids, tokens, late)

    engine.prepare = prepare

    def ds(uids, tokens, greedy=False, rows=None):
        paths.append(("decode_step", len(uids)))
        return orig_ds(uids, tokens, greedy=greedy, rows=rows)

    engine.put, engine.decode_step = put, ds
    return paths


def test_fast_decode_tick_routes_through_decode_step(params):
    """Steady-state greedy decode must NOT pack/upload ragged metadata
    per tick: pure-DECODE ticks go through ``decode_step`` (device-
    resident tables), mixed prefill ticks through ``put``."""
    rng = np.random.default_rng(16)
    prompts = [rng.integers(0, CFG.vocab_size, size=(6,)).tolist()
               for _ in range(2)]
    want = _greedy_reference(params, prompts, n_new=6)

    eng = _engine(params)
    paths = _spy_paths(eng)
    sched = ContinuousBatchScheduler(eng)
    reqs = [sched.submit(p, sampling=SamplingParams(max_new_tokens=6))
            for p in prompts]
    sched.run_until_idle()
    for r, w in zip(reqs, want):
        assert r.state is RequestState.FINISHED
        assert r.generated == w               # device argmax == host argmax
    kinds = [p[0] for p in paths]
    assert kinds[0] == "put"                  # prefill tick
    assert kinds.count("decode_step") == 5    # all-decode ticks
    assert sched.fast_ticks == 5


def test_fast_decode_opt_out_uses_put(params):
    rng = np.random.default_rng(17)
    prompt = rng.integers(0, CFG.vocab_size, size=(6,)).tolist()
    eng = _engine(params)
    paths = _spy_paths(eng)
    sched = ContinuousBatchScheduler(eng, fast_decode=False)
    r = sched.submit(prompt, sampling=SamplingParams(max_new_tokens=4))
    sched.run_until_idle()
    assert r.state is RequestState.FINISHED
    assert all(p[0] == "put" for p in paths)
    assert sched.fast_ticks == 0


def test_fast_decode_stochastic_matches_put_path(params):
    """Non-greedy decode still fast-ticks (logits fetched for the
    host sampler) and draws the same (seed, uid, position)-keyed tokens
    as the put path."""
    rng = np.random.default_rng(18)
    prompt = rng.integers(0, CFG.vocab_size, size=(6,)).tolist()
    sp = SamplingParams(greedy=False, temperature=0.8, top_k=8, seed=3,
                        max_new_tokens=6)

    def run(fast):
        sched = ContinuousBatchScheduler(_engine(params), fast_decode=fast)
        r = sched.submit(prompt, sampling=sp, uid=77)
        sched.run_until_idle()
        assert r.state is RequestState.FINISHED
        return r.generated, sched.fast_ticks

    toks_fast, fast_ticks = run(True)
    toks_slow, slow_ticks = run(False)
    assert toks_fast == toks_slow
    assert fast_ticks == 5 and slow_ticks == 0


def test_fast_decode_survives_preemption_and_mixed_ticks(params):
    """Fast ticks interleaved with preempt/resume put ticks keep the
    device-resident decode state coherent (greedy parity end to end)."""
    rng = np.random.default_rng(19)
    n_req, n_new = 6, 8
    prompts = [rng.integers(0, CFG.vocab_size, size=(int(n),)).tolist()
               for n in rng.integers(6, 16, size=n_req)]
    want = _greedy_reference(params, prompts, n_new)
    eng = _engine(params, token_budget=32, block_size=8, max_context=48,
                  max_seqs=4, num_blocks=7)
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
        assert tick < 2000
    assert sched.metrics.preemptions >= 1
    assert sched.fast_ticks >= 1
    for r, w in zip(reqs, want):
        assert r.generated == w, (r.uid, r.preemptions)


# --------------------------------------------------------------------- #
# Dispatch-ahead on greedy pure-decode ticks: the next step goes to the
# device before this one's tokens are fetched.  The bar: the streams of a
# scheduler that runs ahead equal, token for token and finish reason for
# finish reason, those of one that feeds every decode through ``put``.
# --------------------------------------------------------------------- #
def _ahead_prompts(n, seed=30, lo=5, hi=12):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, CFG.vocab_size, size=(int(k),)).tolist()
            for k in rng.integers(lo, hi, size=n)]


def _greedy(n, **kw):
    return SamplingParams(greedy=True, max_new_tokens=n, **kw)


def _steps(sched, n, in_flight=True):
    """``n`` ticks; then, on a scheduler that runs ahead, a decode step is
    in flight (or the scenario is not testing what it says it is)."""
    for _ in range(n):
        sched.step()
    if sched.fast_decode and in_flight:
        assert sched._inflight is not None


def _sc_length_staggered(sched, mk):
    reqs = [sched.submit(p, _greedy(n))
            for p, n in zip(_ahead_prompts(3), (3, 6, 9))]
    sched.run_until_idle()
    return reqs


def _sc_stop_token_in_flight(sched, mk):
    """A stop token that arrives while the row's next step is in flight:
    nothing is emitted past it, the sequence and its blocks go the tick it
    arrives, and the other row decodes on."""
    a, b = _ahead_prompts(2, seed=31)
    ref = _greedy_reference(sched.engine.params, [a], n_new=8)[0]
    stop = ref[4] if ref.index(ref[4]) >= 2 else ref[2]
    ra = sched.submit(a, _greedy(12, stop_token_ids=(stop,)))
    rb = sched.submit(b, _greedy(10))
    sm = sched.engine.state_manager
    while ra.finish_reason is None:
        sched.step()
    assert ra.generated[-1] == stop and ra.generated.count(stop) == 1
    assert sm.get_sequence(ra.uid) is None
    if sched.fast_decode:       # the step ahead was fed the stop token
        assert ra in sched._inflight.packed
    assert sched.running_decode_uids == [rb.uid]    # settles that step
    seq = sm.get_sequence(rb.uid)
    assert seq.seen_tokens == rb.fed == len(rb.history) - 1
    assert len(seq.blocks) == -(-seq.seen_tokens // 8) == \
        sm.allocator.num_blocks - 1 - sm.free_blocks
    sched.run_until_idle()
    return [ra, rb]


def _sc_max_context(sched, mk):
    # max_context 32: a 20-token prompt ends by context, not by budget
    p = _ahead_prompts(1, seed=32, lo=20, hi=21)[0]
    reqs = [sched.submit(p, _greedy(64)),
            sched.submit(_ahead_prompts(1, seed=33)[0], _greedy(20))]
    sched.run_until_idle()
    assert reqs[0].finish_reason == "length"
    assert len(reqs[0].history) == 32
    return reqs


def _sc_arrival_mid_run(sched, mk):
    a, b = _ahead_prompts(2, seed=34)
    reqs = [sched.submit(a, _greedy(10))]
    _steps(sched, 3)
    reqs.append(sched.submit(b, _greedy(6)))
    sched.run_until_idle()
    return reqs


def _sc_stochastic_joins(sched, mk):
    a, b = _ahead_prompts(2, seed=35)
    reqs = [sched.submit(a, _greedy(10), uid=71)]
    _steps(sched, 3)
    reqs.append(sched.submit(b, SamplingParams(
        greedy=False, temperature=0.8, top_k=8, seed=3, max_new_tokens=6),
        uid=72))
    sched.run_until_idle()
    return reqs


def _sc_preemption_under_kv_pressure(sched, mk):
    prompts = _ahead_prompts(6, seed=19, lo=6, hi=16)
    reqs, tick = [], 0
    while len(reqs) < len(prompts) or sched.num_pending:
        if len(reqs) < len(prompts) and tick % 2 == 0:
            reqs.append(sched.submit(prompts[len(reqs)], _greedy(8)))
        sched.step()
        tick += 1
        assert tick < 2000
    assert sched.metrics.preemptions >= 1
    return reqs


def _sc_handoff_with_kv(sched, mk, include_kv=True):
    a, b = _ahead_prompts(2, seed=36)
    ra, rb = sched.submit(a, _greedy(12)), sched.submit(b, _greedy(9))
    _steps(sched, 4)
    snap, kv = sched.extract_for_handoff(ra.uid, include_kv=include_kv)
    assert sched._inflight is None and (kv is not None) == include_kv
    # the engine is where the requests are: nothing of a step in flight
    sm = sched.engine.state_manager
    assert sm.get_sequence(rb.uid).seen_tokens == rb.fed == \
        len(rb.history) - 1
    if kv is not None:
        assert kv["seen_tokens"] == snap.fed_tokens == \
            len(snap.prompt) + len(snap.generated) - 1
    ra2 = sched.resubmit(snap, kv_state=kv)
    sched.run_until_idle()
    assert ra.finish_reason == "handoff"
    return [ra2, rb]


def _sc_flush_to_host(sched, mk):
    return _sc_handoff_with_kv(sched, mk, include_kv=False)


def _sc_shutdown_drains(sched, mk):
    reqs = [sched.submit(p, _greedy(9)) for p in _ahead_prompts(2, seed=37)]
    _steps(sched, 4)
    assert sched.shutdown(30.0) is True
    assert sched.num_pending == 0 and sched._inflight is None
    sm = sched.engine.state_manager
    assert sm.n_tracked_sequences == 0
    return reqs


def _sc_shutdown_hands_off(sched, mk):
    reqs = [sched.submit(p, _greedy(9)) for p in _ahead_prompts(2, seed=38)]
    _steps(sched, 4)
    drained, snaps = sched.shutdown(0.0, handoff=True)
    assert not drained and len(snaps) == 2 and sched._inflight is None
    assert sched.engine.state_manager.n_tracked_sequences == 0
    other = mk()
    out = [other.resubmit(s) for s in snaps]
    other.run_until_idle()
    assert [r.uid for r in out] == [r.uid for r in reqs]
    return out


def _sc_deadline_expires(sched, mk):
    """The deadline falls due with the row's next step in flight: that
    row of it is dropped, so the request keeps the tokens it had."""
    a, b = _ahead_prompts(2, seed=39)
    ra = sched.submit(a, _greedy(12), deadline_s=500.0)
    rb = sched.submit(b, _greedy(9))
    _steps(sched, 4)
    ra.arrival_time -= 1000.0
    sched.step()
    assert ra.finish_reason == "deadline" and \
        ra.state is RequestState.FAILED
    assert sched.engine.state_manager.get_sequence(ra.uid) is None
    sched.run_until_idle()
    return [ra, rb]


# -- the tick in which a row ends by length sends the next step ahead over
# -- the rows that go on (PR 47) ---------------------------------------- #
def _to_a_finish(sched, first):
    """Tick until ``first`` has ended by length.  A scheduler that runs
    ahead did not stop for it: the decode step after the one that ended it
    is in flight over the rows that go on (or the scenario is not testing
    what it says it is)."""
    while first.finish_reason is None:
        sched.step()
    assert first.finish_reason == "length"
    if sched.fast_decode:
        step = sched._inflight
        assert step is not None and not step.ragged and step.ahead == 1
        assert step.packed and first not in step.packed
        assert [row for _, row in step.rows] == list(range(len(step.packed)))


def _survivor_rows(sched, seed=42):
    """Three rows decoding, the first of them just ended by length."""
    reqs = [sched.submit(p, _greedy(n))
            for p, n in zip(_ahead_prompts(3, seed=seed), (4, 12, 12))]
    _to_a_finish(sched, reqs[0])
    return reqs


def _sc_survivors_go_ahead(sched, mk):
    """Nothing arrives: in each tick in which a row ends, a device-fed
    ``decode_step`` goes out over fewer uids than the step before it, told
    where in that step's tokens each of them stands."""
    reqs = [sched.submit(p, _greedy(n))
            for p, n in zip(_ahead_prompts(4, seed=43), (3, 5, 5, 9))]
    calls = sched.engine.decode_step.calls
    _to_a_finish(sched, reqs[0])
    if sched.fast_decode:
        (before, _), (uids, rows) = calls[-2:]
        assert before == [r.uid for r in reqs] and uids == before[1:]
        assert rows == [1, 2, 3]
    _to_a_finish(sched, reqs[1])
    if sched.fast_decode:                  # two rows ended in one tick
        assert calls[-1] == ([reqs[3].uid], [2])
    sched.run_until_idle()
    if sched.fast_decode:       # the others: all rows, no gather asked for
        assert sum(rows is not None for _, rows in calls) == 2
    return reqs


def _sc_survivors_meet_stop_tokens(sched, mk):
    """Two stop tokens around the step sent ahead over the survivors: one
    arrives in the tick of the finish (its row of that step is dropped, and
    the step after goes out over the rows left, by a gather again), one
    with that step itself."""
    prompts = _ahead_prompts(4, seed=44)
    ref = _greedy_reference(sched.engine.params, prompts, n_new=8)
    stops = [next(t for i, t in enumerate(r) if i >= at and r.index(t) == i)
             for r, at in ((ref[1], 3), (ref[2], 4))]
    at = [ref[1].index(stops[0]), ref[2].index(stops[1])]
    reqs = [sched.submit(prompts[0], _greedy(at[0] + 1)),
            sched.submit(prompts[1], _greedy(12, stop_token_ids=stops[:1])),
            sched.submit(prompts[2], _greedy(12, stop_token_ids=stops[1:])),
            sched.submit(prompts[3], _greedy(10))]
    calls = sched.engine.decode_step.calls
    _to_a_finish(sched, reqs[0])
    assert reqs[1].finish_reason == "stop"      # in that very tick
    if sched.fast_decode:
        assert reqs[1] in sched._inflight.packed
    while reqs[2].finish_reason is None:
        sched.step()
    assert [r.finish_reason for r in reqs] == ["length", "stop", "stop", None]
    assert [r.generated[-1] for r in reqs[1:3]] == stops
    if sched.fast_decode:
        assert [u for u, rows in calls if rows is not None][:2] == \
            [[r.uid for r in reqs[1:]], [r.uid for r in reqs[2:]]]
    sched.run_until_idle()
    return reqs


def _sc_survivors_to_max_context(sched, mk):
    # max_context 32: the 20-token prompt ends by context while the third
    # row goes on, after the first ended by its budget
    prompts = _ahead_prompts(1, seed=45) + \
        _ahead_prompts(1, seed=46, lo=20, hi=21) + _ahead_prompts(1, seed=47)
    reqs = [sched.submit(p, _greedy(n))
            for p, n in zip(prompts, (4, 64, 18))]
    _to_a_finish(sched, reqs[0])
    _to_a_finish(sched, reqs[1])
    assert len(reqs[1].history) == 32
    sched.run_until_idle()
    return reqs


def _sc_survivors_deadline(sched, mk):
    reqs = _survivor_rows(sched)
    had = list(reqs[1].generated)
    reqs[1].deadline_s = 500.0
    reqs[1].arrival_time -= 1000.0
    sched.step()
    assert reqs[1].finish_reason == "deadline" and \
        reqs[1].state is RequestState.FAILED
    # the token of the step in flight over it was dropped, not handed out
    assert reqs[1].generated == had
    assert sched.engine.state_manager.get_sequence(reqs[1].uid) is None
    sched.run_until_idle()
    return reqs


def _sc_survivors_stochastic_arrival(sched, mk):
    """A stochastic request arrives after the finish: no batch can be
    prepared on device tokens for it, the step in flight is returned as a
    decode tick and the arrival joins at level."""
    reqs = _survivor_rows(sched, seed=48)
    reqs.append(sched.submit(_ahead_prompts(1, seed=49)[0], SamplingParams(
        greedy=False, temperature=0.8, top_k=8, seed=3, max_new_tokens=6),
        uid=73))
    before = sched.ragged_ahead_ticks
    sched.run_until_idle()
    assert sched.ragged_ahead_ticks == before
    return reqs


def _sc_survivors_under_kv_pressure(sched, mk):
    """Six usable blocks, staggered budgets and an arrival every other
    tick: rows end while others go on, arrivals are prepared under the
    survivors' step, and the decode set outgrows the pool."""
    prompts = _ahead_prompts(6, seed=19, lo=6, hi=16)
    new = (5, 8, 6, 9, 7, 8)
    reqs, tick = [], 0
    while len(reqs) < len(prompts) or sched.num_pending:
        if len(reqs) < len(prompts) and tick % 2 == 0:
            reqs.append(sched.submit(prompts[len(reqs)],
                                     _greedy(new[len(reqs)])))
        sched.step()
        tick += 1
        assert tick < 2000
    assert sched.metrics.preemptions >= 1
    if sched.fast_decode:
        assert any(rows is not None
                   for _, rows in sched.engine.decode_step.calls)
    return reqs


def _sc_survivors_shutdown_drains(sched, mk):
    reqs = _survivor_rows(sched, seed=50)
    assert sched.shutdown(30.0) is True
    assert sched.num_pending == 0 and sched._inflight is None
    return reqs


_AHEAD_SCENARIOS = [
    _sc_length_staggered, _sc_stop_token_in_flight, _sc_max_context,
    _sc_arrival_mid_run, _sc_stochastic_joins,
    _sc_preemption_under_kv_pressure, _sc_handoff_with_kv,
    _sc_flush_to_host, _sc_shutdown_drains, _sc_shutdown_hands_off,
    _sc_deadline_expires, _sc_survivors_go_ahead,
    _sc_survivors_meet_stop_tokens, _sc_survivors_to_max_context,
    _sc_survivors_deadline, _sc_survivors_stochastic_arrival,
    _sc_survivors_under_kv_pressure, _sc_survivors_shutdown_drains]


@pytest.mark.parametrize("scenario", _AHEAD_SCENARIOS,
                         ids=[f.__name__[4:] for f in _AHEAD_SCENARIOS])
def test_running_ahead_matches_sequential_decode(params, scenario):
    tight = scenario in (_sc_preemption_under_kv_pressure,
                         _sc_survivors_under_kv_pressure)

    def run(fast):
        def mk():
            return ContinuousBatchScheduler(_engine(
                params, max_context=48 if tight else 32,
                num_blocks=7 if tight else None), fast_decode=fast)

        sched = mk()
        ahead = _spy_device_steps(sched.engine)
        reqs = scenario(sched, mk)
        sm = sched.engine.state_manager
        assert sched.num_pending == 0 and sched._inflight is None
        assert sm.n_tracked_sequences == 0
        assert sm.free_blocks == sm.allocator.num_blocks - 1
        return [(r.uid, r.generated, r.finish_reason, r.state)
                for r in reqs], len(ahead)

    got, n_ahead = run(True)
    want, none = run(False)
    assert got == want
    assert n_ahead >= 1 and none == 0     # it did run ahead


def _spy_device_steps(engine):
    """The ``decode_step`` calls fed a device array, the steps dispatched
    ahead: ``(uids, rows)`` of each (also as ``engine.decode_step.calls``)."""
    calls, orig = [], engine.decode_step

    def ds(uids, tokens, greedy=False, rows=None):
        if isinstance(tokens, jax.Array):
            calls.append((list(uids), rows))
        else:
            assert rows is None
        return orig(uids, tokens, greedy=greedy, rows=rows)

    ds.calls = calls
    engine.decode_step = ds
    return calls


@pytest.mark.parametrize("new", [(9, 9), (3, 9)],
                         ids=["same_rows", "survivors"])
def test_failed_fetch_of_a_step_ahead_recovers(params, monkeypatch, new):
    """The fetch of a step that was dispatched ahead fails (over the same
    rows as the step before it, or over the row that goes on after the
    other ended by length): the error comes out of the tick that owns the
    step, nothing of it or of the step behind it stays in the engine, and
    the rows recompute to the streams they would have had."""
    prompts = _ahead_prompts(2, seed=40)
    want = [w[:n] for w, n in
            zip(_greedy_reference(params, prompts, n_new=9), new)]
    eng = _engine(params)
    sched = ContinuousBatchScheduler(eng)
    reqs = [sched.submit(p, _greedy(n)) for p, n in zip(prompts, new)]
    _steps(sched, 3)
    before = [list(r.generated) for r in reqs]
    live = [r for r in reqs if r.finish_reason is None]
    assert len(live) == (1 if new[0] == 3 else 2)
    real_fetch, recovered = sched._fetch, []
    real_recover = eng._recover_donated_cache

    def failing(arr, launch):
        monkeypatch.setattr(sched, "_fetch", real_fetch)
        raise RuntimeError("device lost")

    monkeypatch.setattr(sched, "_fetch", failing)
    monkeypatch.setattr(eng, "_recover_donated_cache",
                        lambda: (recovered.append(1), real_recover()))
    assert sched._inflight.ahead == 1 and sched._inflight.packed == live
    with pytest.raises(RuntimeError, match="device lost"):
        sched.step()
    assert recovered == [1] and sched._inflight is None
    sm = eng.state_manager
    assert sm.n_tracked_sequences == 0 and eng._dev_decode_state is None
    assert sm.free_blocks == sm.allocator.num_blocks - 1
    assert [r.generated for r in reqs] == before      # nothing handed out
    assert all(r.state is RequestState.PREEMPTED for r in live)
    sched.run_until_idle()
    assert [r.generated for r in reqs] == want
    assert sm.n_tracked_sequences == 0


def test_prefix_cache_registers_the_same_blocks_running_ahead(params):
    """A sequence decoded ahead is fed device tokens whose values reach
    the host a tick later: its decoded blocks enter the radix tree all
    the same, and a stop token's row registers nothing past the stop."""
    prompt = _ahead_prompts(1, seed=41, lo=9, hi=10)[0]
    ref = _greedy_reference(params, [prompt], n_new=30)[0]
    # stop where the fed history ends one short of a full block: a row
    # decoded one step too far would fill it
    cut = next(i for i in range(14, 30)
               if (len(prompt) + i) % 8 == 7 and ref.index(ref[i]) == i)

    def run(fast):
        cfg = RaggedInferenceEngineConfig.from_dict({
            "state_manager": {"max_ragged_batch_size": 32,
                              "max_ragged_sequence_count": 4,
                              "max_context": 64},
            "kv_cache": {"block_size": 8, "enable_prefix_cache": True}})
        eng = InferenceEngineV2(RaggedLlama(CFG, 8), params, cfg)
        sched = ContinuousBatchScheduler(eng, fast_decode=fast)
        r = sched.submit(prompt, _greedy(30, stop_token_ids=(ref[cut],)))
        stopped = []
        while sched.num_pending:
            sched.step()
            seq = eng.state_manager.get_sequence(r.uid)
            if seq is not None:
                stopped.append(seq.register_stopped)
        assert r.finish_reason == "stop" and r.generated == ref[:cut + 1]
        pc = eng.state_manager.prefix_cache
        return (pc.cached_blocks, pc.match_len(r.history), any(stopped),
                sorted(pc.node_tokens(n) for n in pc._iter_nodes()
                       if n.block is not None))

    ahead, seq = run(True), run(False)
    assert ahead == seq
    blocks, matched, stopped, _ = ahead
    fed = len(prompt) + cut             # the stop token itself is never fed
    assert not stopped and blocks == fed // 8 and matched == 8 * blocks


def test_running_ahead_builds_no_new_program(params):
    """Arrivals, finishes, a preemption: the programs the engine built are
    the ones it built before any step ran ahead — one ``decode_step``, the
    ragged buckets — and the decode program's text does not depend on who
    fed it."""
    def run(fast):
        eng = _engine(params, token_budget=32, max_context=48, num_blocks=7)
        sched = ContinuousBatchScheduler(eng, fast_decode=fast)
        _sc_preemption_under_kv_pressure(sched, None)
        return eng

    ahead, seq = run(True), run(False)
    assert set(ahead.step_keys) == set(seq.step_keys) | {("decode_step",)}
    assert set(ahead.step_keys) <= {("decode_step",), (16, None), (32, None)}
    fed_by_host = _engine(params, token_budget=32, max_context=48,
                          num_blocks=7)
    fed_by_host.put([1], [[3, 4, 5]])
    fed_by_host.decode_step([1], [6], greedy=True)
    assert ahead.lower_step(("decode_step",)).as_text() == \
        fed_by_host.lower_step(("decode_step",)).as_text()


# --------------------------------------------------------------------- #
# The next ragged batch is packed and prepared while the program before it
# runs, and launched on its tokens (PR 40).  The bar is the same: request by
# request the streams, finish reasons and states of the fully sequential
# scheduler (``fast_decode=False``: pack, ``put``, fetch, advance, a tick
# at a time), and an empty engine afterwards.
# --------------------------------------------------------------------- #
def _tick_until(sched, cond, limit=200):
    """Tick until ``cond()``.  A scheduler that runs ahead then has a
    ragged step in flight (or the scenario is not testing what it says it
    is); the sequential one is level, and has handed out as much."""
    n = 0
    while not cond():
        sched.step()
        n += 1
        assert n < limit
    if sched.fast_decode:
        assert sched._inflight is not None and sched._inflight.ragged


def _long(n, seed, lo=40, hi=70):
    return _ahead_prompts(n, seed=seed, lo=lo, hi=hi)


def _staggered(sched, prompts, new, every=2, **kw):
    """Submit one prompt every ``every`` ticks, then run dry."""
    reqs, tick = [], 0
    while len(reqs) < len(prompts) or sched.num_pending:
        if len(reqs) < len(prompts) and tick % every == 0:
            n = new[len(reqs)] if isinstance(new, (list, tuple)) else new
            reqs.append(sched.submit(prompts[len(reqs)], _greedy(n, **kw)))
        sched.step()
        tick += 1
        assert tick < 2000
    return reqs


def _rg_mixed_after_mixed(sched, mk):
    """Prompts longer than the budget (32) arriving staggered beside rows
    that decode: every tick runs a ragged batch for a long while."""
    return _staggered(sched, _long(4, 50), (9, 7, 8, 6))


def _rg_mixed_after_decode(sched, mk):
    """An arrival while a decode step is in flight: its batch is built
    under that step."""
    a, = _ahead_prompts(1, seed=51)
    reqs = [sched.submit(a, _greedy(14))]
    _steps(sched, 3)
    before = sched.ragged_ahead_ticks
    reqs.append(sched.submit(_long(1, 52)[0], _greedy(5)))
    sched.step()
    if sched.fast_decode:
        assert sched.ragged_ahead_ticks == before + 1
        assert sched._inflight.ragged
    sched.run_until_idle()
    return reqs


def _rg_stop_token_on_late_row(sched, mk):
    """A row that decodes beside a long prompt's chunks emits its stop
    token from a ragged step: the batch prepared under that step holds its
    next row and is discarded, nothing past the stop is emitted, and the
    prompt goes on."""
    a, = _ahead_prompts(1, seed=53)
    ref = _greedy_reference(sched.engine.params, [a], n_new=8)[0]
    stop = next(t for i, t in enumerate(ref) if i >= 3 and ref.index(t) == i)
    ra = sched.submit(a, _greedy(12, stop_token_ids=(stop,)))
    _steps(sched, 2, in_flight=False)
    rb = sched.submit(_long(1, 54, lo=150, hi=151)[0], _greedy(4))
    while ra.finish_reason is None:
        sched.step()
    assert ra.finish_reason == "stop" and ra.generated[-1] == stop \
        and ra.generated.count(stop) == 1
    assert sched.engine.state_manager.get_sequence(ra.uid) is None
    if sched.fast_decode:
        assert sched.ragged_discards >= 1
    sched.run_until_idle()
    return [ra, rb]


def _rg_ends_inside_the_step(sched, mk):
    """``max_new_tokens=1`` rows and a row that reaches ``max_context``
    (64) end with the token of the step in flight: they are left out of
    the batch prepared under it."""
    prompts = _long(3, 55, lo=36, hi=50) + \
        _ahead_prompts(1, seed=56, lo=58, hi=59)
    reqs = _staggered(sched, prompts, (1, 6, 1, 30), every=1)
    assert reqs[3].finish_reason == "length" and \
        len(reqs[3].history) == 64
    return reqs


def _rg_stochastic_joins(sched, mk):
    """A stochastic request joins under a ragged step in flight: the tick
    falls back to the logits, and the streams are the sequential ones."""
    reqs = [sched.submit(p, _greedy(8), uid=81 + i)
            for i, p in enumerate(_long(2, 57))]
    _tick_until(sched, lambda: len(reqs[0].generated) >= 1)
    reqs.append(sched.submit(_ahead_prompts(1, seed=58)[0], SamplingParams(
        greedy=False, temperature=0.8, top_k=8, seed=3, max_new_tokens=6),
        uid=91))
    sched.run_until_idle()
    return reqs


def _rg_preemption_under_kv_pressure(sched, mk):
    """The decode set no longer fits with a ragged step in flight: that
    step is settled and the ordinary tick preempts."""
    reqs = _staggered(sched, _ahead_prompts(6, seed=19, lo=10, hi=22), 10)
    assert sched.metrics.preemptions >= 1
    return reqs


def _rg_handoff_with_kv(sched, mk, include_kv=True):
    ra = sched.submit(_ahead_prompts(1, seed=59)[0], _greedy(12))
    rb = sched.submit(_long(1, 60, lo=120, hi=121)[0], _greedy(5))
    _tick_until(sched, lambda: len(ra.generated) >= 3)
    snap, kv = sched.extract_for_handoff(ra.uid, include_kv=include_kv)
    assert sched._inflight is None and (kv is not None) == include_kv
    sm = sched.engine.state_manager
    assert sm.get_sequence(rb.uid).seen_tokens == rb.fed
    ra2 = sched.resubmit(snap, kv_state=kv)
    sched.run_until_idle()
    assert ra.finish_reason == "handoff"
    return [ra2, rb]


def _rg_flush_to_host(sched, mk):
    return _rg_handoff_with_kv(sched, mk, include_kv=False)


def _rg_shutdown_hands_off(sched, mk):
    reqs = [sched.submit(_ahead_prompts(1, seed=61)[0], _greedy(9)),
            sched.submit(_long(1, 62, lo=120, hi=121)[0], _greedy(5))]
    _tick_until(sched, lambda: len(reqs[0].generated) >= 3)
    drained, snaps = sched.shutdown(0.0, handoff=True)
    assert not drained and len(snaps) == 2 and sched._inflight is None
    assert sched.engine.state_manager.n_tracked_sequences == 0
    other = mk()
    out = [other.resubmit(s) for s in snaps]
    other.run_until_idle()
    assert [r.uid for r in out] == [r.uid for r in reqs]
    return out


def _rg_deadline_expires(sched, mk):
    """The deadline falls due with the row's next ragged step in flight:
    its row of that step is dropped, the request keeps what it had."""
    ra = sched.submit(_ahead_prompts(1, seed=63)[0], _greedy(12),
                      deadline_s=500.0)
    rb = sched.submit(_long(1, 64, lo=120, hi=121)[0], _greedy(5))
    _tick_until(sched, lambda: len(ra.generated) >= 3)
    had = list(ra.generated)
    ra.arrival_time -= 1000.0
    sched.step()
    assert ra.finish_reason == "deadline" and \
        ra.state is RequestState.FAILED and ra.generated == had
    assert sched.engine.state_manager.get_sequence(ra.uid) is None
    sched.run_until_idle()
    return [ra, rb]


def _rg_prefix_hit_under_a_step(sched, mk):
    """Requests that share their first two blocks are admitted under a
    program in flight: they attach what the requests before them cached,
    the blocks of the step in flight among it."""
    rng = np.random.default_rng(65)
    head = rng.integers(0, CFG.vocab_size, size=(16,)).tolist()
    prompts = [head + rng.integers(0, CFG.vocab_size,
                                   size=(int(n),)).tolist()
               for n in (30, 41, 25, 38)]
    reqs = _staggered(sched, prompts, 6, every=1)
    assert sched.engine.prefix_cache_stats.hit_tokens >= 16 * 2
    return reqs


def _rg_stateful_model(sched, mk):
    """State slots (LFM2's convolution tails): a chunk prepared under the
    program that writes the tail it starts from."""
    import test_ragged_lfm2 as lfm2
    prompts = [lfm2._ids(n, seed=70 + i).tolist()
               for i, n in enumerate((100, 130, 40, 90))]
    return _staggered(sched, prompts, (6, 5, 7, 4))


def _rg_grouped_pool_model(sched, mk):
    """Two block tables a sequence (Trinity's window layers): window blocks
    are released while the batch is prepared, under the program that reads
    the band."""
    import test_kv_groups as groups
    prompts = [groups.ids(n, seed=80 + i).tolist()
               for i, n in enumerate((100, 70, 120, 50))]
    return _staggered(sched, prompts, (6, 5, 7, 4))


def _rg_closed_loop(sched, mk):
    """Three callers, each submitting its next request the moment its last
    one is done, as the benchmark's closed loop does: every arrival follows
    a finish by length, finds a program in flight (the decode step that was
    sent ahead over the rows that go on, or the ragged step launched by
    the tick that handed the last token out) and its batch is prepared
    under that program."""
    rng = np.random.default_rng(68)
    budgets = [[3, 4, 6, 3], [5, 3, 4, 5], [18]]
    busy, reqs, arrivals = [None] * len(budgets), [], 0
    start = sched.ragged_ahead_ticks
    while any(busy) or any(budgets):
        for c, r in enumerate(busy):
            if r is not None and r.finish_reason is not None:
                busy[c] = None
            if busy[c] is None and budgets[c]:
                if len(reqs) >= len(budgets):       # it follows a finish
                    arrivals += 1
                    assert sched._inflight is not None or \
                        not sched.fast_decode
                busy[c] = sched.submit(
                    rng.integers(0, CFG.vocab_size,
                                 size=(int(rng.integers(5, 20)),)).tolist(),
                    _greedy(budgets[c].pop(0)))
                reqs.append(busy[c])
        sched.step()
    assert arrivals == 6
    if sched.fast_decode:
        assert sched.ragged_ahead_ticks - start == arrivals
    return reqs


def _lfm2_engine():
    import test_ragged_lfm2 as lfm2
    return lfm2._engine(lfm2._seeded_params(), max_seqs=8)


def _afmoe_engine():
    import test_kv_groups as groups
    return groups.engine(groups.params(), budget=32, tile=16, seqs=4)


_RAGGED_SCENARIOS = {
    _rg_mixed_after_mixed: {}, _rg_mixed_after_decode: {},
    _rg_stop_token_on_late_row: dict(max_context=192),
    _rg_ends_inside_the_step: dict(max_context=64),
    _rg_stochastic_joins: {},
    _rg_preemption_under_kv_pressure: dict(max_context=48, num_blocks=7),
    _rg_handoff_with_kv: {}, _rg_flush_to_host: {},
    _rg_shutdown_hands_off: {}, _rg_deadline_expires: {},
    _rg_prefix_hit_under_a_step: dict(prefix=True),
    _rg_closed_loop: {},
    _rg_stateful_model: dict(engine=_lfm2_engine),
    _rg_grouped_pool_model: dict(engine=_afmoe_engine)}


def _ragged_engine(params, engine=None, prefix=False, max_context=128,
                   num_blocks=None):
    if engine is not None:
        return engine()
    cfg = RaggedInferenceEngineConfig.from_dict({
        "state_manager": {"max_ragged_batch_size": 32,
                          "max_ragged_sequence_count": 4,
                          "max_context": max_context},
        "kv_cache": {"block_size": 8, "enable_prefix_cache": prefix,
                     **({"num_blocks": num_blocks} if num_blocks else {})}})
    return InferenceEngineV2(RaggedLlama(CFG, 8), params, cfg)


@pytest.mark.parametrize("scenario", _RAGGED_SCENARIOS,
                         ids=[f.__name__[4:] for f in _RAGGED_SCENARIOS])
def test_ragged_batches_prepared_ahead_match_the_sequential_scheduler(
        params, scenario):
    how = _RAGGED_SCENARIOS[scenario]

    def run(fast):
        def mk():
            return ContinuousBatchScheduler(_ragged_engine(params, **how),
                                            fast_decode=fast)

        sched = mk()
        reqs = scenario(sched, mk)
        sm = sched.engine.state_manager
        assert sched.num_pending == 0 and sched._inflight is None
        assert sm.n_tracked_sequences == 0
        assert sm.free_blocks == sm.allocator.num_blocks - 1
        if sm.window is not None:
            assert sm.win_allocator.free_blocks == \
                sm.win_allocator.num_blocks - 1
        return [(r.uid, r.generated, r.finish_reason, r.state)
                for r in reqs], sched.ragged_ahead_ticks

    got, n_ahead = run(True)
    want, none = run(False)
    assert got == want
    assert n_ahead >= 1 and none == 0     # batches were prepared ahead


def test_failed_fetch_of_a_ragged_step_in_flight_recovers(params,
                                                          monkeypatch):
    """The fetch of a ragged step in flight fails under the batch prepared
    behind it: the error comes out of the tick that would have returned
    its tokens, the preparation is dropped with it, nothing of either
    stays in the engine, and every request recomputes to the stream it
    would have had."""
    prompts = _ahead_prompts(1, seed=66) + _long(1, 67, lo=56, hi=57)
    want = _greedy_reference(params, prompts, n_new=7)
    eng = _ragged_engine(params)
    sched = ContinuousBatchScheduler(eng)
    reqs = [sched.submit(p, _greedy(7)) for p in prompts]
    _tick_until(sched, lambda: len(reqs[0].generated) >= 1)
    before = [list(r.generated) for r in reqs]
    real_fetch, recovered, dropped = sched._fetch, [], []
    real_recover, real_discard = eng._recover_donated_cache, eng.discard

    def failing(arr, launch):
        monkeypatch.setattr(sched, "_fetch", real_fetch)
        raise RuntimeError("device lost")

    monkeypatch.setattr(sched, "_fetch", failing)
    monkeypatch.setattr(eng, "_recover_donated_cache",
                        lambda: (recovered.append(1), real_recover()))
    monkeypatch.setattr(eng, "discard",
                        lambda b: (dropped.append(1), real_discard(b)))
    with pytest.raises(RuntimeError, match="device lost"):
        sched.step()
    assert recovered == [1] and dropped == [1] and sched._inflight is None
    sm = eng.state_manager
    assert sm.n_tracked_sequences == 0
    assert sm.free_blocks == sm.allocator.num_blocks - 1
    assert [r.generated for r in reqs] == before      # nothing handed out
    assert all(r.state is RequestState.PREEMPTED for r in reqs)
    sched.run_until_idle()
    assert [r.generated for r in reqs] == want
    assert sm.n_tracked_sequences == 0


# --------------------------------------------------------------------- #
# What a ``put`` tick fetches follows its rows: the step program's argmax
# (one int32 a row) when every packed row is greedy, the logits for the
# host sampler when one is not.  The bar: request by request the tokens of
# the logits path.
# --------------------------------------------------------------------- #
def _spy_greedy(engine, force_logits=False):
    """Record what every ragged forward was asked for: a ``put``, or the
    scheduler's own ``prepare`` + ``launch`` (all-greedy rows: the token
    vector).  With ``force_logits`` a greedy ask is answered as every tick
    once was: the logits of the same program fetched, ``np.argmax`` on the
    host."""
    asked = []
    orig, orig_prepare, orig_launch = \
        engine.put, engine.prepare, engine.launch
    mine = []       # batches the scheduler prepared itself

    def put(uids, tokens, sync=True, greedy=False):
        asked.append(greedy)
        if greedy and force_logits:
            rows = orig(uids, tokens, sync=sync)
            return {u: int(np.argmax(r)) for u, r in rows.items()}
        return orig(uids, tokens, sync=sync, greedy=greedy)

    def prepare(uids, tokens=None, late=()):
        out = orig_prepare(uids, tokens, late)
        if tokens is not None:
            asked.append(True)
            mine.append(out)
        return out

    def launch(prepared, late_tokens=None):
        logits, nxt, n = orig_launch(prepared, late_tokens)
        if force_logits and any(prepared is p for p in mine):
            nxt = np.argmax(np.asarray(logits, np.float32), axis=-1)
        return logits, nxt, n

    engine.put, engine.prepare, engine.launch = put, prepare, launch
    return asked


def _tied(params):
    """Every odd column of the head a copy of the even one before it: each
    row's maximum is held by two indices, bit for bit."""
    kernel = np.array(params["lm_head"]["kernel"])
    kernel[:, 1::2] = kernel[:, 0::2]
    return {**params, "lm_head": {"kernel": jnp.asarray(kernel)}}


_FETCH_CASES = {
    # prompts join requests that are decoding, one every other tick
    "joins": dict(lens=(13, 7, 21, 5, 11), new=6),
    "tied_maxima": dict(lens=(13, 7, 21, 5, 11), new=6, tied=True),
    # 6 usable blocks for requests of up to 3: recompute after preemption
    "preempted": dict(lens=(9, 14, 7, 12, 10, 8), new=8, num_blocks=7,
                      max_context=48),
    # 16 tokens of every prompt are two cached blocks after the first
    "prefix_hit": dict(lens=(20, 19, 23, 18), new=5, prefix=16),
}


def _serve_case(params, case, force_logits):
    spec = _FETCH_CASES[case]
    rng = np.random.default_rng(41)
    head = rng.integers(0, CFG.vocab_size, size=(spec.get("prefix", 0),))
    prompts = [head.tolist() + rng.integers(
        0, CFG.vocab_size, size=(n - len(head),)).tolist()
        for n in spec["lens"]]
    cfg = RaggedInferenceEngineConfig.from_dict({
        "state_manager": {"max_ragged_batch_size": 32,
                          "max_ragged_sequence_count": 4,
                          "max_context": spec.get("max_context", 64)},
        "kv_cache": {"block_size": 8,
                     "enable_prefix_cache": "prefix" in spec,
                     **({"num_blocks": spec["num_blocks"]}
                        if "num_blocks" in spec else {})}})
    eng = InferenceEngineV2(
        RaggedLlama(CFG, 8), _tied(params) if spec.get("tied") else params,
        cfg)
    asked = _spy_greedy(eng, force_logits)
    sched = ContinuousBatchScheduler(eng)
    reqs, tick = [], 0
    while len(reqs) < len(prompts) or sched.num_pending:
        if len(reqs) < len(prompts) and tick % 2 == 0:
            reqs.append(sched.submit(prompts[len(reqs)],
                                     sampling=_greedy(spec["new"])))
        sched.step()
        tick += 1
        assert tick < 2000
    return reqs, asked, sched, eng


@pytest.mark.parametrize("case", sorted(_FETCH_CASES))
def test_greedy_put_ticks_emit_the_tokens_of_the_logits_path(params, case):
    reqs, asked, sched, eng = _serve_case(params, case, force_logits=False)
    want, asked_w, _, _ = _serve_case(params, case, force_logits=True)
    # the same ticks, every one of them asked for tokens
    assert asked == asked_w and asked and all(asked)
    for got, ref in zip(reqs, want):
        assert got.state is RequestState.FINISHED
        assert (got.generated, got.finish_reason, got.preemptions) == \
            (ref.generated, ref.finish_reason, ref.preemptions), got.uid
    spec = _FETCH_CASES[case]
    if spec.get("tied"):
        assert all(t % 2 == 0 for r in reqs for t in r.generated)
    if "num_blocks" in spec:
        assert sched.metrics.preemptions >= 1 and \
            any(r.preemptions for r in reqs)
    if "prefix" in spec:
        assert eng.prefix_cache_stats.hit_tokens >= \
            spec["prefix"] * (len(reqs) - 2)


def _draws_alone(params, prompt, sp, uid):
    """The (seed, uid, position)-keyed draws of one stochastic request by
    the plain loop: ``put`` for logits, ``sample_batch`` on the host."""
    eng = _engine(params, token_budget=64)
    feed, toks = list(prompt), []
    for pos in range(sp.max_new_tokens):
        row = eng.put([uid], [feed])[uid]
        feed = [int(sample_batch(row[None], [sp], [pos], [uid])[0])]
        toks += feed
    return toks


def test_one_stochastic_row_sends_its_ticks_to_the_logits(params):
    """A stochastic request beside greedy ones: every ``put`` tick that
    packs it fetches logits and it draws what it draws alone; the ticks
    without it fetch tokens; the greedy requests emit what they emit with
    no stochastic row anywhere (``joins`` above)."""
    spec = _FETCH_CASES["joins"]
    greedy_alone, _, _, _ = _serve_case(params, "joins", force_logits=False)
    prompts = [list(r.prompt) for r in greedy_alone]
    sp = SamplingParams(greedy=False, temperature=0.8, top_k=8, seed=3,
                        max_new_tokens=9)
    rng = np.random.default_rng(42)
    noisy_prompt = rng.integers(0, CFG.vocab_size, size=(10,)).tolist()

    eng = _engine(params, max_seqs=4)
    orig, orig_prepare, asked = eng.put, eng.prepare, []

    def put(uids, tokens, sync=True, greedy=False):
        asked.append((greedy, 77 in uids))
        return orig(uids, tokens, sync=sync, greedy=greedy)

    def prepare(uids, tokens=None, late=()):
        if tokens is not None:      # the scheduler's own: a greedy batch
            asked.append((True, 77 in uids))
        return orig_prepare(uids, tokens, late)

    eng.put, eng.prepare = put, prepare
    sched = ContinuousBatchScheduler(eng)
    reqs, noisy, tick = [], None, 0
    while len(reqs) < len(prompts) or sched.num_pending:
        if len(reqs) < len(prompts) and tick % 2 == 0:
            reqs.append(sched.submit(prompts[len(reqs)],
                                     sampling=_greedy(spec["new"])))
        if tick == 3:
            noisy = sched.submit(noisy_prompt, sampling=sp, uid=77)
        sched.step()
        tick += 1
        assert tick < 2000
    assert {True, False} == {g for g, _ in asked}
    assert all(greedy != packed_noisy for greedy, packed_noisy in asked)
    assert noisy.generated == _draws_alone(params, noisy_prompt, sp, 77)
    for got, ref in zip(reqs, greedy_alone):
        assert got.generated == ref.generated, got.uid


# --------------------------------------------------------------------- #
# The tier-1 smoke (tools/serving_smoke.py)
# --------------------------------------------------------------------- #
def _load_smoke():
    path = pathlib.Path(__file__).resolve().parents[2] / "tools" / \
        "serving_smoke.py"
    spec = importlib.util.spec_from_file_location("serving_smoke", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_serving_smoke_tool():
    snap = _load_smoke().run_smoke()
    assert snap["finished"] == 8 and snap["preemptions"] >= 1


def test_prefix_router_smoke_tool():
    snap = _load_smoke().run_prefix_router_smoke()
    assert snap["router_smoke"] == "ok"
    assert snap["router_cache_hits"] >= 6


def test_speculative_smoke_tool():
    snap = _load_smoke().run_speculative_smoke()
    assert snap["speculative_smoke"] == "ok"
    assert snap["spec_accept_rate"] > 0
    assert snap["spec_tokens_per_pass"] >= 1
