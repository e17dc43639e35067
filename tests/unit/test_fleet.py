"""Serving-fleet matrix: request-snapshot handoff round trips, the
drain-then-handoff shutdown mode, prefill→decode KV migration
(bit-identical decode vs the colocated path), rolling restarts with
admission open, zero-loss replica kill/replay, queue-depth elasticity
over synthetic series, the merged ``fleet/*`` telemetry namespace, and
the subprocess chaos smoke (``tools/fleet_smoke.py``) behind a hard
timeout.

Correctness bar throughout: greedy token-for-token parity with an
uninterrupted single-replica run over the same engine params — a killed,
drained, migrated, or disaggregated request must emit the exact stream
it would have emitted had nothing happened.
"""

import dataclasses
import json
import os
import pathlib
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.fleet import (AdmissionBudget, BreakerState,
                                 CircuitBreaker, CrashBlame,
                                 FleetAutoscaler, FleetMetrics,
                                 OverloadShedError, ServingFleet)
from deepspeed_tpu.inference.v2 import (InferenceEngineV2,
                                        RaggedInferenceEngineConfig)
from deepspeed_tpu.inference.v2.model_implementations import RaggedLlama
from deepspeed_tpu.models import LlamaConfig, LlamaForCausalLM
from deepspeed_tpu.resilience import chaos
from deepspeed_tpu.resilience.supervisor import RestartBudget
from deepspeed_tpu.serving import (CacheAwareRouter,
                                   ContinuousBatchScheduler, Request,
                                   RequestSnapshot, RequestState,
                                   SamplingParams, TickDeadlineError)

CFG = LlamaConfig.tiny(dtype=jnp.float32)
_TOOL = pathlib.Path(__file__).resolve().parents[2] / "tools" / \
    "fleet_smoke.py"

GEN = 5


@pytest.fixture(scope="module")
def params():
    return LlamaForCausalLM(CFG).init(
        jax.random.key(0), np.zeros((1, 4), np.int32))["params"]


def _sched(params, num_blocks=17, prefix_cache=False, max_queue=None,
           tick_deadline_s=None):
    cfg = RaggedInferenceEngineConfig.from_dict({
        "state_manager": {"max_ragged_batch_size": 32,
                          "max_ragged_sequence_count": 4,
                          "max_context": 48},
        "kv_cache": {"block_size": 8, "num_blocks": num_blocks,
                     **({"enable_prefix_cache": True} if prefix_cache
                        else {})},
    })
    return ContinuousBatchScheduler(
        InferenceEngineV2(RaggedLlama(CFG, 8), params, cfg),
        max_queue=max_queue, tick_deadline_s=tick_deadline_s)


def _prompts(n=3, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, CFG.vocab_size, size=(int(k),)).tolist()
            for k in rng.integers(8, 16, size=n)]


@pytest.fixture(scope="module")
def gold(params):
    """Uninterrupted single-replica greedy streams for _prompts()."""
    sched = _sched(params)
    samp = SamplingParams(greedy=True, max_new_tokens=GEN)
    reqs = [sched.submit(p, sampling=samp) for p in _prompts()]
    sched.run_until_idle()
    assert all(r.state is RequestState.FINISHED for r in reqs)
    return [r.generated for r in reqs]


# --------------------------------------------------------------------- #
# RequestSnapshot
# --------------------------------------------------------------------- #
def test_snapshot_json_roundtrip_preserves_replay_state():
    samp = SamplingParams(greedy=False, temperature=0.7, top_k=9,
                          max_new_tokens=12, stop_token_ids=(3, 5),
                          seed=42)
    req = Request(uid=77, prompt=[1, 2, 3], sampling=samp, priority=4,
                  deadline_s=30.0)
    req.generated = [10, 11]
    req.tenant = "acme"
    snap = RequestSnapshot.from_json(req.snapshot().to_json())
    assert snap.uid == 77 and snap.history == [1, 2, 3, 10, 11]
    assert snap.tenant == "acme" and snap.priority == 4
    # the deadline travels as REMAINING budget
    assert 0 < snap.deadline_s <= 30.0
    back = snap.to_request()
    assert back.uid == 77 and back.generated == [10, 11]
    assert back.state is RequestState.QUEUED
    assert back.sampling == samp      # tuple stop ids restored from JSON
    assert back.history == [1, 2, 3, 10, 11]


def test_snapshot_deadline_never_resets():
    req = Request(uid=1, prompt=[1], deadline_s=100.0)
    req.arrival_time -= 40.0          # 40s already burned
    snap = req.snapshot()
    assert 59.0 < snap.deadline_s < 61.0


# --------------------------------------------------------------------- #
# Drain-handoff shutdown + resubmit
# --------------------------------------------------------------------- #
def test_drain_handoff_roundtrip_parity(params, gold):
    """Half-served requests handed off mid-flight finish token-exactly on
    another replica; the source releases every KV block and keeps no
    'shutdown' failures.  Also covers: resubmit of a live uid rejects,
    and a fully-drained handoff shutdown returns (True, [])."""
    a, b = _sched(params), _sched(params)
    samp = SamplingParams(greedy=True, max_new_tokens=GEN)
    ra = [a.submit(p, sampling=samp) for p in _prompts()]
    for _ in range(3):      # mid-stream once the step in flight settles
        a.step()
    drained, snaps = a.shutdown(0.0, handoff=True)
    assert not drained and len(snaps) == 3
    assert a.metrics.handoffs == 3 and a.metrics.shutdown_failed == 0
    # old objects are terminal here; the continuation is a NEW object
    assert all(r.state is RequestState.HANDED_OFF for r in ra)
    assert all(r.finish_reason == "handoff" for r in ra)
    sm = a.engine.state_manager
    assert sm.n_tracked_sequences == 0
    assert sm.free_blocks == sm.allocator.num_blocks - 1
    with pytest.raises(RuntimeError, match="shutting down"):
        a.submit([1, 2, 3], sampling=samp)
    uid_order = [r.uid for r in ra]
    rb = {r.uid: r for r in (b.resubmit(s) for s in snaps)}
    with pytest.raises(ValueError, match="already live"):
        b.resubmit(snaps[0])               # uid is live on b now
    b.run_until_idle()
    for i, uid in enumerate(uid_order):
        assert rb[uid].state is RequestState.FINISHED
        assert rb[uid].generated == gold[i], i
    drained, snaps = b.shutdown(30.0, handoff=True)
    assert drained and snaps == []


def test_handoff_parity_with_stochastic_sampling(params):
    """(seed, uid, position)-keyed noise + preserved uid ⇒ a replayed
    stochastic request draws the SAME tokens it would have drawn."""
    samp = SamplingParams(greedy=False, temperature=0.8, top_k=20,
                          max_new_tokens=GEN, seed=7)
    ref_sched = _sched(params)
    ref = ref_sched.submit(_prompts()[0], sampling=samp, uid=901)
    ref_sched.run_until_idle()

    a = _sched(params)
    r = a.submit(_prompts()[0], sampling=samp, uid=901)
    for _ in range(3):
        a.step()
    assert 0 < len(r.generated) < GEN, "pick a tick count mid-stream"
    _, snaps = a.shutdown(0.0, handoff=True)
    # target = ref_sched: uid 901 finished there, so it's free again —
    # resubmission onto a replica that served the uid before must work
    r2 = ref_sched.resubmit(snaps[0])
    ref_sched.run_until_idle()
    assert r2.generated == ref.generated


# --------------------------------------------------------------------- #
# KV handoff: prefill→decode migration
# --------------------------------------------------------------------- #
def test_engine_kv_state_moves_between_engines(params):
    """flush_to_host(include_kv=True) → resume(kv_state=...) on a SECOND
    engine reproduces bit-identical logits without re-prefilling; plus
    the resume-argument validation."""
    e1 = _sched(params).engine
    e2 = _sched(params).engine
    prompt = _prompts()[0]
    logits1 = e1.put([5], [prompt])
    tok = int(np.argmax(logits1[5]))
    snap = e1.flush_to_host([5], include_kv=True)[5]
    assert snap["seen_tokens"] == len(prompt)
    assert "kv" in snap
    out = e2.resume(5, prompt, kv_state=snap)
    assert out == {}                  # nothing left to feed
    # continuation logits on the carried KV are BIT-identical to the
    # colocated continuation
    cont1 = e1.resume(5, prompt + [tok])       # recompute path on e1
    with pytest.raises(RuntimeError, match="still live"):
        e2.resume(5, prompt, kv_state=snap)
    cont2 = e2.put([5], [[tok]])
    assert np.array_equal(np.asarray(cont1[5]), np.asarray(cont2[5]))
    with pytest.raises(ValueError, match="covers"):
        e2.resume(9, [1, 2], kv_state={"seen_tokens": 5, "kv": {}})


def test_scheduler_kv_handoff_bit_identical_decode(params, gold):
    """The disaggregated core: prefill on A, extract WITH KV the moment
    the request enters DECODE, resubmit on B — B feeds exactly one token
    (no re-prefill) and the decode stream matches the colocated path."""
    a, b = _sched(params), _sched(params)
    samp = SamplingParams(greedy=True, max_new_tokens=GEN)
    r = a.submit(_prompts()[0], sampling=samp)
    while r.uid not in a.running_decode_uids:
        a.step()
    snap, kv = a.extract_for_handoff(r.uid, include_kv=True)
    assert kv is not None and snap.fed_tokens == kv["seen_tokens"]
    assert snap.generated == r.generated and len(r.generated) >= 1
    r2 = b.resubmit(snap, kv_state=kv)
    # KV injected: only the unfed tail (1 token) remains to feed
    assert r2.fed == kv["seen_tokens"] and r2.remaining_feed == 1
    b.run_until_idle()
    assert r2.state is RequestState.FINISHED
    assert r2.generated == gold[0]
    assert b.metrics.finished == 1


def test_kv_handoff_falls_back_to_recompute_when_pool_full(params):
    """When the target replica cannot place the carried KV RIGHT NOW
    (its pool is occupied), the payload is dropped and the request
    queues as a recompute replay — slower, never lost."""
    rng = np.random.default_rng(11)
    p_occupant = rng.integers(0, CFG.vocab_size, size=(17,)).tolist()
    p_handoff = rng.integers(0, CFG.vocab_size, size=(14,)).tolist()
    samp = SamplingParams(greedy=True, max_new_tokens=GEN)

    a = _sched(params)
    b = _sched(params, num_blocks=5)   # 4 usable blocks
    occ = b.submit(p_occupant, sampling=samp)
    while occ.uid not in b.running_decode_uids:
        b.step()                       # occupant now pins 3 blocks
    assert b.engine.state_manager.free_blocks == 1

    # explicit fleet-style uid: both schedulers' auto-counters start at 1
    r = a.submit(p_handoff, sampling=samp, uid=501)
    while r.uid not in a.running_decode_uids:
        a.step()
    snap, kv = a.extract_for_handoff(r.uid, include_kv=True)
    assert -(-kv["seen_tokens"] // 8) == 2     # needs 2 blocks, 1 free
    r2 = b.resubmit(snap, kv_state=kv)
    assert r2.fed == 0                 # payload dropped: recompute replay
    b.run_until_idle()
    assert r2.state is RequestState.FINISHED
    # uninterrupted reference on a — already compiled, now idle
    rr = a.submit(p_handoff, sampling=samp, uid=777)
    a.run_until_idle()
    assert r2.generated == rr.generated


# --------------------------------------------------------------------- #
# ServingFleet: disaggregated pools
# --------------------------------------------------------------------- #
def test_disaggregated_fleet_matches_colocated(params, gold):
    fleet = ServingFleet(lambda name: _sched(params),
                         prefill_replicas=1, decode_replicas=2)
    samp = SamplingParams(greedy=True, max_new_tokens=GEN)
    frs = [fleet.submit(p, sampling=samp) for p in _prompts()]
    fleet.run_until_idle(max_ticks=300)
    for i, fr in enumerate(frs):
        assert fr.state == "finished", (fr.uid, fr.state, fr.finish_reason)
        assert fr.tokens == gold[i], i
        assert fr.handoffs >= 1 and fr.replica.startswith("decode")
    snap = fleet.snapshot()
    assert snap["fleet/handoffs"] >= 3.0
    assert snap["fleet/p50_handoff_s"] > 0.0
    assert snap["fleet/replicas_prefill"] == 1.0
    assert snap["fleet/replicas_decode"] == 2.0
    # prefill pool is empty once everything migrated
    assert snap["fleet/pending_prefill"] == 0.0


def test_fleet_rejects_half_disaggregated_config(params):
    with pytest.raises(ValueError, match="BOTH"):
        ServingFleet(lambda name: _sched(params), prefill_replicas=2)


# --------------------------------------------------------------------- #
# ServingFleet: rolling restarts + kill/replay
# --------------------------------------------------------------------- #
@pytest.mark.slow
def test_rolling_restart_admission_open_zero_lost(params, gold):
    """Marked slow: the tier-1 budget gets this exact scenario (3-replica
    upgrade wave, admission open, zero lost, greedy-exact) from
    ``tools/fleet_smoke.py``'s upgrade variant via test_fleet_smoke_tool;
    this finer-grained twin runs in unfiltered/deep test runs."""
    fleet = ServingFleet(lambda name: _sched(params), replicas=3)
    samp = SamplingParams(greedy=True, max_new_tokens=GEN)
    frs = [fleet.submit(p, sampling=samp) for p in _prompts()]
    for _ in range(2):
        fleet.step()
    waves = []

    def on_wave(name):
        # mid-upgrade submissions must be accepted (admission open)
        waves.append(fleet.submit(_prompts()[0], sampling=samp))
        assert not {r.name for _, r in fleet.pool_members()} - \
            set(fleet.replica_names)

    handed = fleet.rolling_restart(drain_deadline_s=0.0, on_wave=on_wave)
    assert len(handed) == 3 and sum(handed.values()) >= 3
    fleet.run_until_idle(max_ticks=300)
    for i, fr in enumerate(frs):
        assert fr.state == "finished" and fr.tokens == gold[i], (i, fr)
    for fr in waves:
        assert fr.state == "finished" and fr.tokens == gold[0]
    assert fleet.snapshot()["fleet/rolling_restarts"] == 1.0


def test_kill_replica_replays_in_flight_zero_lost(params, gold):
    fleet = ServingFleet(lambda name: _sched(params), replicas=2)
    samp = SamplingParams(greedy=True, max_new_tokens=GEN)
    frs = [fleet.submit(p, sampling=samp) for p in _prompts()]
    for _ in range(3):
        fleet.step()
    victim = next(fr.replica for fr in frs if not fr.done)
    replayed = fleet.kill_replica(victim)
    assert replayed >= 1
    fleet.run_until_idle(max_ticks=300)
    for i, fr in enumerate(frs):
        assert fr.state == "finished", (fr.uid, fr.state)
        assert fr.tokens == gold[i], i
    snap = fleet.snapshot()
    assert snap["fleet/restarts"] == 1.0
    assert snap["fleet/replayed_requests"] == float(replayed)
    assert snap["fleet/requests_failed"] == 0.0


def test_rolling_restart_collects_finishes_during_drain(params, gold):
    """A request that COMPLETES inside a wave's drain window must be
    journaled before the old scheduler is discarded — otherwise the
    client handle stays 'live' forever and run_until_idle spins."""
    fleet = ServingFleet(lambda name: _sched(params), replicas=1)
    samp = SamplingParams(greedy=True, max_new_tokens=GEN)
    frs = [fleet.submit(p, sampling=samp) for p in _prompts()]
    fleet.rolling_restart(drain_deadline_s=30.0)   # everything drains
    assert fleet.num_pending == 0
    for i, fr in enumerate(frs):
        assert fr.state == "finished" and fr.tokens == gold[i], (i, fr)


def test_kill_replica_releases_tenant_quota(params):
    from deepspeed_tpu.serving import TenantQuota

    fleet = ServingFleet(
        lambda name: _sched(params), replicas=1, keep_finished=2,
        router_kwargs={"quotas": {"acme": TenantQuota(max_inflight=1)}})
    samp = SamplingParams(greedy=True, max_new_tokens=GEN)
    fr0 = fleet.submit(_prompts()[0], tenant="acme", sampling=samp)
    fleet.step()
    fleet.kill_replica(fleet.replica_names[0])
    fleet.run_until_idle(max_ticks=300)
    assert fr0.state == "finished"
    # the stranded pre-kill Request object must not count against the
    # tenant forever: with max_inflight=1, a fresh submit only fits if
    # the killed incarnation was released
    fr = fleet.submit(_prompts()[0], tenant="acme", sampling=samp)
    fleet.run_until_idle(max_ticks=300)
    assert fr.state == "finished"
    # keep_finished retention prunes the oldest finished journal entries
    for p in _prompts(3, seed=9):
        fleet.submit(p, sampling=samp)
    fleet.run_until_idle(max_ticks=300)
    assert fleet.num_pending == 0
    assert len(fleet.requests) == 2        # oldest finished pruned


# --------------------------------------------------------------------- #
# Elasticity
# --------------------------------------------------------------------- #
def test_autoscaler_synthetic_series_up_down_hysteresis():
    a = FleetAutoscaler(min_replicas=1, max_replicas=4,
                        scale_up_backlog=100, scale_down_backlog=10,
                        patience=2, max_moves=10)
    hi = {"fleet/queue_depth_mixed": 1000.0}
    lo = {"fleet/queue_depth_mixed": 0.0}
    mid = {"fleet/queue_depth_mixed": 50.0 * 2}   # between the bars
    # one hot sample is noise; two (patience) trigger the move
    assert a.observe(hi, 2, now=0.0) == 2
    assert a.observe(hi, 2, now=1.0) == 3
    # mid-band resets both streaks
    assert a.observe(mid, 3, now=2.0) == 3
    assert a.observe(lo, 3, now=3.0) == 3
    assert a.observe(lo, 3, now=4.0) == 2
    assert a.observe(lo, 2, now=5.0) == 2
    assert a.observe(lo, 2, now=6.0) == 1
    assert a.observe(lo, 1, now=7.0) == 1         # floor holds


def test_autoscaler_budget_bounds_churn():
    a = FleetAutoscaler(min_replicas=1, max_replicas=8,
                        scale_up_backlog=100, scale_down_backlog=10,
                        patience=1, max_moves=1, move_window_s=100.0)
    hi = {"fleet/queue_depth_mixed": 1000.0}
    assert a.observe(hi, 1, now=0.0) == 2
    assert a.observe(hi, 2, now=1.0) == 2          # budget spent: hold
    assert a.held_by_budget == 1
    assert a.observe(hi, 2, now=200.0) == 3        # window slid: earned back


def test_autoscaler_snaps_to_elastic_config():
    # micro=1, ceiling 12 -> valid worlds {1,2,3,4,6,12}: 5 is illegal,
    # so an upsize from 4 lands on 6
    elastic = {"elasticity": {"enabled": True, "max_train_batch_size": 12,
                              "micro_batch_sizes": [1], "version": 0.1}}
    a = FleetAutoscaler(min_replicas=1, max_replicas=8,
                        scale_up_backlog=100, scale_down_backlog=10,
                        patience=1, max_moves=10, elastic_config=elastic)
    hi = {"fleet/queue_depth_mixed": 10000.0}
    assert a.observe(hi, 4, now=0.0) == 6


def test_autoscaler_rejects_bad_config():
    with pytest.raises(ValueError, match="below"):
        FleetAutoscaler(scale_up_backlog=10, scale_down_backlog=10)
    with pytest.raises(ValueError, match="bounds"):
        FleetAutoscaler(min_replicas=3, max_replicas=2)


def test_fleet_elastic_resize_migrates_work(params, gold):
    fleet = ServingFleet(lambda name: _sched(params), replicas=2)
    samp = SamplingParams(greedy=True, max_new_tokens=GEN)
    frs = [fleet.submit(p, sampling=samp) for p in _prompts()]
    for _ in range(2):
        fleet.step()
    fleet.set_replica_count(3)
    assert len(fleet.replica_names) == 3
    fleet.set_replica_count(1)        # downsize drains + migrates
    assert len(fleet.replica_names) == 1
    fleet.run_until_idle(max_ticks=300)
    for i, fr in enumerate(frs):
        assert fr.state == "finished" and fr.tokens == gold[i], (i, fr)
    snap = fleet.snapshot()
    assert snap["fleet/scale_ups"] == 1.0
    assert snap["fleet/scale_downs"] == 2.0


def test_fleet_autoscaler_integration_scales_up_under_backlog(params):
    auto = FleetAutoscaler(min_replicas=1, max_replicas=3,
                           scale_up_backlog=8, scale_down_backlog=1,
                           patience=1, max_moves=10)
    fleet = ServingFleet(lambda name: _sched(params), replicas=1,
                         autoscaler=auto, autoscale_every=1)
    samp = SamplingParams(greedy=True, max_new_tokens=GEN)
    for p in _prompts(4, seed=3):
        fleet.submit(p, sampling=samp)
    fleet.step()                       # backlog >> bar: upsize fires
    assert len(fleet.replica_names) >= 2
    fleet.run_until_idle(max_ticks=300)
    assert all(fr.state == "finished" for fr in fleet.requests)


def test_fleet_drain_stall_escalates_to_handoff(params, gold):
    """A downsize victim that stops making drain progress (``drain_stall``
    chaos, ``drop`` = the drain step is suppressed) is escalated at the
    drain deadline: leftovers hand off to survivors, nothing is lost."""
    fleet = ServingFleet(lambda name: _sched(params), replicas=2)
    samp = SamplingParams(greedy=True, max_new_tokens=GEN)
    frs = [fleet.submit(p, sampling=samp) for p in _prompts()]
    fleet.step()
    with chaos.inject("drain_stall", "drop", count=0):
        fleet.set_replica_count(1, drain_deadline_s=0.2)
    snap = fleet.snapshot()
    assert snap["fleet/scale_drain_escalations"] == 1.0
    assert snap["fleet/scale_down_drain_s"] >= 0.2
    fleet.run_until_idle(max_ticks=300)
    for i, fr in enumerate(frs):
        assert fr.state == "finished" and fr.tokens == gold[i], (i, fr)
    assert all(fr.replays == 0 for fr in frs)   # handoff, not replay


def test_fleet_scale_spawn_slow_records_latency(params):
    fleet = ServingFleet(lambda name: _sched(params), replicas=1)
    with chaos.inject("scale_spawn_slow", sleep_s=0.15, count=0):
        fleet.set_replica_count(2)
    assert len(fleet.replica_names) == 2
    snap = fleet.snapshot()
    assert snap["fleet/scale_ups"] == 1.0
    assert snap["fleet/scale_up_spawn_s"] >= 0.15


# --------------------------------------------------------------------- #
# Telemetry + router elasticity plumbing
# --------------------------------------------------------------------- #
def test_fleet_metrics_namespace_and_export(params):
    fleet = ServingFleet(lambda name: _sched(params), replicas=1)
    samp = SamplingParams(greedy=True, max_new_tokens=2)
    fleet.submit(_prompts()[0], sampling=samp)
    fleet.run_until_idle(max_ticks=100)
    events = fleet.export_metrics()
    names = {n for n, _, _ in events}
    assert names and all(n.startswith("fleet/") for n in names)
    for want in ("fleet/replicas", "fleet/queue_depth_mixed",
                 "fleet/goodput_tokens_per_s", "fleet/restarts",
                 "fleet/handoffs", "fleet/requests_finished",
                 "fleet/router_replicas"):
        assert want in names, want
    # wall-clock x values, like every serving/* series
    assert all(isinstance(x, float) and x > 1e9 for _, _, x in events)


def test_router_skips_draining_replica(params):
    s1, s2 = _sched(params), _sched(params)
    router = CacheAwareRouter({"a": s1, "b": s2})
    s1.shutdown(0.0)
    samp = SamplingParams(greedy=True, max_new_tokens=2)
    for _ in range(3):
        req = router.submit(_prompts()[0], sampling=samp)
        assert req.replica == "b"
    s2.shutdown(0.0)
    with pytest.raises(RuntimeError, match="draining"):
        router.submit(_prompts()[0], sampling=samp)


def test_router_add_remove_replace_replicas(params):
    s1, s2 = _sched(params), _sched(params)
    router = CacheAwareRouter({"a": s1})
    router.add_replica("b", s2)
    with pytest.raises(ValueError, match="already present"):
        router.add_replica("b", s2)
    assert {r.name for r in router.replicas} == {"a", "b"}
    router.remove_replica("a")
    with pytest.raises(ValueError, match="unknown"):
        router.remove_replica("a")
    with pytest.raises(ValueError, match="last replica"):
        router.remove_replica("b")
    s3 = _sched(params)
    router.replace_replica("b", s3)
    assert router.replicas[0].scheduler is s3


# --------------------------------------------------------------------- #
# Defense in depth: crash blame, circuit breakers, admission budget
# (pure policy units — synthetic traces, injected clocks)
# --------------------------------------------------------------------- #
def test_crash_blame_scoring_isolation_and_conviction():
    b = CrashBlame(suspect_after=2, convict_after=2)
    b.record_death([1, 2, 3], replica="r0")
    assert b.suspects() == [] and b.convict([1, 2, 3]) is None
    b.record_death([1, 4], replica="r1")
    assert b.is_suspect(1) and not b.is_suspect(2)
    # co-batched deaths never convict — only a singleton in-flight set
    assert b.convict([1, 4]) is None
    # at 2 deaths an UN-probed singleton escalates to a suspect, it does
    # not convict (two operator kills of a lone request are not proof);
    # the same evidence from a deliberate isolation probe convicts
    assert b.convict([1]) is None
    assert b.convict([1], probed=True) == 1
    b.record_death([1], replica="r0")
    assert b.convict([1]) == 1           # 3rd death: convicts un-probed
    # the shared partition both death paths apply
    convicted, suspects, innocents = b.classify_lost({1})
    assert convicted == 1 and suspects == [] and innocents == []
    convicted, suspects, innocents = b.classify_lost({1, 2})
    assert convicted is None and suspects == [1] and innocents == [2]
    # a singleton death of a FIRST-time offender does not convict
    b2 = CrashBlame()
    b2.record_death([9])
    assert b2.convict([9]) is None and b2.convict([9], probed=True) is None
    # the journal keeps the exact in-flight set per death
    assert [d["uids"] for d in b.deaths] == [[1, 2, 3], [1, 4], [1]]
    # absolution clears the score; new evidence reopens the case
    b.absolve(4)
    assert not b.is_suspect(4) and b.death_count(4) == 0
    b.record_death([4, 5])
    assert b.death_count(4) == 1
    b.forget(1)
    assert b.death_count(1) == 0


def test_circuit_breaker_open_halfopen_close_cycle():
    now = [0.0]
    cb = CircuitBreaker(failure_threshold=2, cooloff_s=10.0,
                        cooloff_factor=2.0, clock=lambda: now[0])
    assert cb.state is BreakerState.CLOSED and cb.allows()
    assert cb.record_failure() is False          # 1/2: still closed
    assert cb.record_failure() is True           # 2/2: OPEN
    assert cb.state is BreakerState.OPEN and not cb.allows()
    now[0] = 9.9
    assert not cb.allows()
    now[0] = 10.0                                # cooloff elapsed
    assert cb.state is BreakerState.HALF_OPEN and cb.allows()
    assert cb.record_failure() is True           # probe failed: re-OPEN
    assert cb.cooloff_s == 20.0                  # escalated
    assert not cb.allows()
    now[0] = 30.0
    assert cb.state is BreakerState.HALF_OPEN
    cb.record_success()                          # probe succeeded
    assert cb.state is BreakerState.CLOSED and cb.failures == 0
    assert cb.cooloff_s == 10.0                  # cooloff reset
    cb.trip()                                    # force-open (budget out)
    assert not cb.allows() and cb.opens == 3


def test_admission_budget_sheds_lowest_class_first():
    a = AdmissionBudget(max_backlog_tokens=100.0)
    a.admit(10, "batch", backlog_tokens=0)       # 10 <= 50: fine
    with pytest.raises(OverloadShedError) as ei:
        a.admit(10, "batch", backlog_tokens=45)  # 55 > 50: shed
    assert ei.value.retry_after_s > 0 and ei.value.shed_class == "batch"
    a.admit(10, "standard", backlog_tokens=45)   # 55 <= 85
    a.admit(10, "interactive", backlog_tokens=85)  # 95 <= 100
    with pytest.raises(OverloadShedError):
        a.admit(10, "interactive", backlog_tokens=95)
    snap = a.snapshot()
    assert snap["admitted"] == 3.0 and snap["shed_total"] == 2.0
    assert snap["shed_batch"] == 1.0 and snap["shed_interactive"] == 1.0
    # retry-after derives from the measured drain rate when given
    with pytest.raises(OverloadShedError) as ei:
        a.admit(20, "batch", backlog_tokens=50, drain_tokens_per_s=10.0)
    assert ei.value.retry_after_s == pytest.approx(2.0)  # 20 excess / 10


def test_admission_budget_rate_gate_class_floors():
    now = [0.0]
    a = AdmissionBudget(admit_tokens_per_s=10.0, burst_tokens=100.0,
                        clock=lambda: now[0])
    a.admit(40, "batch")                  # level 100 -> 60 (floor 50)
    with pytest.raises(OverloadShedError) as ei:
        a.admit(20, "batch")              # would cross batch's 50 floor
    assert ei.value.retry_after_s == pytest.approx(1.0)  # 10 short @ 10/s
    a.admit(20, "interactive")            # floor 0: 60 -> 40
    now[0] = 2.0                          # refill 20 tokens -> 60
    a.admit(10, "batch")                  # 60 -> 50, at the floor exactly
    with pytest.raises(OverloadShedError):
        a.admit(1, "batch")
    with pytest.raises(ValueError, match="needs"):
        AdmissionBudget()
    with pytest.raises(ValueError, match="ceilings"):
        AdmissionBudget(max_backlog_tokens=10, default_ceiling=1.5)


# --------------------------------------------------------------------- #
# Defense in depth, integrated: poison quarantine, breaker, watchdog,
# replay budget, overload — all in-process with chaos fault points
# --------------------------------------------------------------------- #
def test_poison_request_quarantined_innocents_exact(params, gold):
    """A request that deterministically crashes the engine whenever it is
    batched must be convicted via blame+isolation within <= 3 respawns;
    every innocent (including co-batched ones) finishes greedy-exact."""
    fleet = ServingFleet(lambda name: _sched(params), replicas=2)
    samp = SamplingParams(greedy=True, max_new_tokens=GEN)
    frs = [fleet.submit(p, sampling=samp) for p in _prompts()]
    poison = fleet.submit(list(range(1, 11)), sampling=samp)
    chaos.arm("poison_request", "raise", key=str(poison.uid), count=0)
    try:
        fleet.run_until_idle(max_ticks=500)
    finally:
        chaos.disarm("poison_request")
    assert poison.state == "failed"
    assert poison.finish_reason == "quarantined"
    assert poison.error and "quarantined" in poison.error
    from deepspeed_tpu.fleet import QuarantinedError
    with pytest.raises(QuarantinedError, match="quarantined"):
        poison.check()
    for i, fr in enumerate(frs):
        assert fr.state == "finished", (fr.uid, fr.state, fr.finish_reason)
        assert fr.tokens == gold[i], i
    snap = fleet.snapshot()
    assert 1.0 <= snap["fleet/restarts"] <= 3.0
    assert snap["fleet/quarantined"] == 1.0
    assert snap["fleet/isolation_probes"] >= 1.0
    assert snap["fleet/deaths_crash"] == snap["fleet/restarts"]
    # the journal recorded every death's exact in-flight set
    assert all(poison.uid in d["uids"] for d in fleet.blame.deaths)


def test_poison_quarantined_in_disaggregated_fleet(params, gold):
    """A poison that crashes only once DECODING (chaos after=1 skips its
    prefill pack) kills a DECODE replica first; the blame/isolation
    pipeline must still converge — and a suspect under probe is never
    pumped off its isolation replica into the decode pool's traffic."""
    fleet = ServingFleet(lambda name: _sched(params),
                         prefill_replicas=1, decode_replicas=2)
    samp = SamplingParams(greedy=True, max_new_tokens=GEN)
    frs = [fleet.submit(p, sampling=samp) for p in _prompts()]
    poison = fleet.submit(list(range(1, 11)), sampling=samp)
    chaos.arm("poison_request", "raise", key=str(poison.uid), count=0,
              after=1)
    try:
        fleet.run_until_idle(max_ticks=800)
    finally:
        chaos.disarm("poison_request")
    assert poison.state == "failed"
    assert poison.finish_reason == "quarantined"
    for i, fr in enumerate(frs):
        assert fr.state == "finished", (fr.uid, fr.state, fr.finish_reason)
        assert fr.tokens == gold[i], i
    snap = fleet.snapshot()
    assert snap["fleet/quarantined"] == 1.0
    assert 1.0 <= snap["fleet/restarts"] <= 3.0


def test_spawn_fail_opens_breaker_without_eating_budget(params, gold):
    """Respawn failures open the replica's circuit breaker: the replica
    leaves placement (capacity degrades), the fleet restart budget stays
    intact, and a half-open probe recovers it once spawning works."""
    budget = RestartBudget(max_restarts=8, window_s=120.0)
    fleet = ServingFleet(lambda name: _sched(params), replicas=2,
                         restart_budget=budget,
                         breaker_kwargs={"failure_threshold": 2,
                                         "cooloff_s": 0.05})
    samp = SamplingParams(greedy=True, max_new_tokens=GEN)
    frs = [fleet.submit(p, sampling=samp) for p in _prompts()]
    for _ in range(2):
        fleet.step()
    chaos.arm("spawn_fail", "raise", count=0)
    try:
        fleet.kill_replica("replica0")
        fleet.run_until_idle(max_ticks=500)
        snap = fleet.snapshot()
        assert snap["fleet/breaker_opens"] >= 1.0
        assert snap["fleet/replicas_broken"] == 1.0
        assert not budget.exhausted()
        # router still places on the survivor, never raises
        fr_live = fleet.submit(_prompts()[0], sampling=samp)
        fleet.run_until_idle(max_ticks=500)
        assert fr_live.state == "finished" and fr_live.tokens == gold[0]
    finally:
        chaos.disarm("spawn_fail")
    for i, fr in enumerate(frs):
        assert fr.state == "finished" and fr.tokens == gold[i], (i, fr)
    # fault cleared: cooloff elapses, the half-open probe respawns it
    import time as _time
    _time.sleep(0.1)
    fleet.step()
    assert fleet.snapshot()["fleet/replicas_broken"] == 0.0


def test_tick_watchdog_names_batch_and_fleet_recovers(params, gold):
    """A tick slower than tick_deadline_s raises TickDeadlineError naming
    the packed uids; the fleet treats it as a death (reason tick_stall,
    distinct from crash), blames exactly that batch, and recovers."""
    sched = _sched(params, tick_deadline_s=2.0)
    samp = SamplingParams(greedy=True, max_new_tokens=GEN)
    req = sched.submit(_prompts()[0], sampling=samp)
    chaos.arm("tick_stall", "sleep", sleep_s=2.2, count=1)
    try:
        with pytest.raises(TickDeadlineError) as ei:
            sched.step()
    finally:
        chaos.disarm("tick_stall")
    assert ei.value.uids == [req.uid]
    assert ei.value.elapsed_s > ei.value.deadline_s
    assert sched.tick_deadline_trips == 1

    fleet = ServingFleet(lambda n: _sched(params, tick_deadline_s=3.0),
                         replicas=2)
    frs = [fleet.submit(p, sampling=samp) for p in _prompts()]
    chaos.arm("tick_stall", "sleep", sleep_s=3.5, count=1)
    try:
        fleet.run_until_idle(max_ticks=500)
    finally:
        chaos.disarm("tick_stall")
    snap = fleet.snapshot()
    # >= not ==: a genuinely slow tick on a loaded CI host may trip the
    # watchdog again — also a death, also recovered from
    assert snap["fleet/deaths_tick_stall"] >= 1.0
    for i, fr in enumerate(frs):
        assert fr.state == "finished" and fr.tokens == gold[i], (i, fr)


def test_replay_budget_caps_unconvicted_replays(params):
    """Even a request the blame tracker never convicts cannot replay
    unboundedly: past max_replays it fails reason="replay_budget".
    (Blame thresholds raised so two kills don't convict the lone
    in-flight request first — the cap must bind on its own.)"""
    fleet = ServingFleet(lambda name: _sched(params), replicas=2,
                         max_replays=1,
                         blame=CrashBlame(suspect_after=4,
                                          convict_after=4))
    samp = SamplingParams(greedy=True, max_new_tokens=GEN)
    fr = fleet.submit(_prompts()[0], sampling=samp)
    fleet.step()
    fleet.kill_replica(fr.replica)       # replay 1/1
    assert not fr.done and fr.replays == 1
    fleet.kill_replica(fr.replica)       # budget exhausted
    assert fr.state == "failed" and fr.finish_reason == "replay_budget"
    assert fr.error and "max_replays" in fr.error
    assert fleet.snapshot()["fleet/replay_budget_failed"] == 1.0
    assert fleet.num_pending == 0


def test_fleet_overload_sheds_batch_first_with_retry_hint(params):
    fleet = ServingFleet(
        lambda name: _sched(params), replicas=2,
        admission=AdmissionBudget(max_backlog_tokens=60.0))
    samp = SamplingParams(greedy=True, max_new_tokens=GEN)
    fleet.submit(_prompts()[0], priority_class="batch", sampling=samp)
    with pytest.raises(OverloadShedError) as ei:
        fleet.submit(_prompts()[1], priority_class="batch", sampling=samp)
    assert ei.value.retry_after_s > 0
    # the lowest class is at its ceiling; interactive still has headroom
    fr = fleet.submit(_prompts()[1], priority_class="interactive",
                      sampling=samp)
    snap = fleet.snapshot()
    assert snap["fleet/shed_total"] == 1.0
    assert snap["fleet/shed_batch"] == 1.0
    fleet.run_until_idle(max_ticks=300)
    assert fr.state == "finished"


def test_router_skips_breaker_open_replica(params):
    s1, s2 = _sched(params), _sched(params)
    router = CacheAwareRouter({"a": s1, "b": s2})
    rep_a = next(r for r in router.replicas if r.name == "a")
    rep_a.breaker = CircuitBreaker(failure_threshold=1, cooloff_s=60.0)
    rep_a.breaker.record_failure()
    assert not rep_a.available
    samp = SamplingParams(greedy=True, max_new_tokens=2)
    for _ in range(3):
        assert router.submit(_prompts()[0], sampling=samp).replica == "b"
    rep_b = next(r for r in router.replicas if r.name == "b")
    rep_b.broken = True
    with pytest.raises(RuntimeError, match="available"):
        router.submit(_prompts()[0], sampling=samp)


# --------------------------------------------------------------------- #
# Deadline carryover: a killed/replayed or handed-off request resumes
# with its REMAINING deadline, never a fresh one
# --------------------------------------------------------------------- #
def _live_request(fleet, uid):
    for _, rep in fleet.pool_members():
        sched = rep.scheduler
        for req in [*sched._queued, *sched._running.values(),
                    *sched._preempted]:
            if req.uid == uid:
                return req
    return None


def test_deadline_carryover_through_kill_replay(params):
    fleet = ServingFleet(lambda name: _sched(params), replicas=2)
    samp = SamplingParams(greedy=True, max_new_tokens=GEN)
    fr = fleet.submit(_prompts()[0], sampling=samp, deadline_s=30.0)
    fleet.step()
    # burn 10s of the budget (rewind arrival on BOTH views of the clock)
    fr.arrival -= 10.0
    req0 = _live_request(fleet, fr.uid)
    req0.arrival_time -= 10.0
    fleet.kill_replica(fr.replica)
    req1 = _live_request(fleet, fr.uid)
    assert req1 is not None and req1 is not req0
    # the replay resumed with the ~20s REMAINING (minus real serving
    # time since submit), never a fresh 30s
    assert 10.0 < req1.deadline_s < 20.5
    fleet.run_until_idle(max_ticks=300)
    assert fr.state == "finished"


def test_deadline_carryover_through_rolling_restart(params):
    fleet = ServingFleet(lambda name: _sched(params), replicas=2)
    samp = SamplingParams(greedy=True, max_new_tokens=GEN)
    fr = fleet.submit(_prompts()[0], sampling=samp, deadline_s=30.0)
    fleet.step()
    _live_request(fleet, fr.uid).arrival_time -= 10.0
    fleet.rolling_restart(drain_deadline_s=0.0)
    req1 = _live_request(fleet, fr.uid)
    assert req1 is not None
    assert 10.0 < req1.deadline_s < 20.5
    fleet.run_until_idle(max_ticks=300)
    assert fr.state == "finished"


def test_deadline_carryover_through_kv_handoff(params):
    """Disaggregated-style migration: the snapshot built the tick a
    prefill completes carries the REMAINING deadline with the KV."""
    a, b = _sched(params), _sched(params)
    samp = SamplingParams(greedy=True, max_new_tokens=GEN)
    r = a.submit(_prompts()[0], sampling=samp, deadline_s=30.0)
    while r.uid not in a.running_decode_uids:
        a.step()
    r.arrival_time -= 10.0
    snap, kv = a.extract_for_handoff(r.uid, include_kv=True)
    assert 10.0 < snap.deadline_s < 20.5
    r2 = b.resubmit(snap, kv_state=kv)
    assert 10.0 < r2.deadline_s < 20.5
    b.run_until_idle()
    assert r2.state is RequestState.FINISHED


# --------------------------------------------------------------------- #
# The tier-1 chaos smoke: real subprocess workers, SIGKILL mid-decode,
# rolling upgrade — behind a HARD timeout so a fleet bug can't hang CI.
# --------------------------------------------------------------------- #
def test_fleet_smoke_tool():
    proc = subprocess.run(
        [sys.executable, str(_TOOL)],
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
        capture_output=True, text=True, timeout=340)
    assert proc.returncode == 0, \
        f"stdout:\n{proc.stdout[-3000:]}\nstderr:\n{proc.stderr[-3000:]}"
    lines = [ln for ln in proc.stdout.splitlines()
             if ln.startswith('{"fleet_smoke"')]
    assert lines, proc.stdout[-2000:]
    snap = json.loads(lines[-1])
    assert snap["fleet_smoke"] == "ok"
    assert snap["kill_replayed_requests"] >= 1
    assert snap["kill_recovery_s"] < 180.0
    assert snap["upgrade_waves"] == 3
    # defense-in-depth variants (quarantine / breaker / backpressure)
    assert 1 <= snap["poison_respawns"] <= 3
    assert snap["poison_deaths_journaled"] >= 1
    assert snap["spawn_fail_breaker_opens"] >= 1
    assert snap["spawn_fail_budget_used"] < snap["spawn_fail_budget_max"]
    assert snap["overload_shed_batch"] > 0
    assert snap["overload_shed_interactive"] == 0
    assert (snap["overload_p95_interactive_ttft_loaded_s"]
            <= max(2.0 * snap["overload_p95_interactive_ttft_unloaded_s"],
                   0.5))
