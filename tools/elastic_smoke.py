"""Elastic-capacity smoke (~3-5 min CPU): prove scale decisions are real
EVENTS under live traffic — capacity actually appears and disappears,
downsizes drain gracefully, in-flight SSE streams survive the churn, and
when capacity CANNOT arrive the brownout ladder degrades quality instead
of letting the fleet fall over.

Four variants over the same tiny-Llama serving workload (single-device
engines):

**soak** — a diurnal open-loop trace (two day/night swings) replayed
against a 1-replica in-process :class:`ServingFleet` wearing the full
elastic stack: :class:`FleetAutoscaler` (spawns/retires REAL replicas
through the factory), :class:`BrownoutController` (staged degradation
while capacity arrives), and an :class:`AdmissionBudget` (class-first
shedding).  Asserts: at least one scale-up AND one scale-down happened
mid-traffic, the brownout ladder engaged and fully disengaged after the
peak, ZERO admitted requests failed, zero replays (healthy downsizes
migrate by handoff, they do not crash-replay), and zero interactive
sheds below brownout stage 5.

**streams** — three live SSE generations through the HTTP gateway while
the fleet is forced through a scale-up and a double scale-down (short
drain deadline, so leftovers migrate mid-stream).  Asserts: every stream
ends in a ``done`` terminal with gap-free positions and greedy-exact
tokens, zero duplicate tokens suppressed (handoffs resume, they do not
re-emit), and zero replays.

**spawn-fail brownout** — ``spawn_fail`` chaos makes every elastic
scale-up attempt fail while a backlog piles onto one replica.  Asserts:
the scale breaker records the failures (and opens), the fleet NEVER
crashes a tick, the brownout ladder goes deeper instead (capacity cannot
arrive, quality gives), every admitted request still finishes
greedy-exact, and the ladder fully disengages once the backlog drains.

**subprocess** — a :class:`FleetFrontEnd` of REAL subprocess workers
takes two scale-ups (``add_worker`` → spawned, warm-started from the
shared checkpoint, first-heartbeat-gated) and two scale-downs: one
graceful (``remove_worker`` with a generous drain deadline — zero
replays, zero escalations, the victim finishes its own work) and one
chaotic (the draining victim is SIGKILLed mid-drain — the journal
replays its leftovers onto survivors, zero requests lost).  Rides along:
the satellite deadline regression — a request whose ``deadline_s``
expires ON a subprocess worker surfaces through the HTTP gateway as a
typed ``deadline`` SSE error event.

Wired into tier-1 via ``tests/unit/test_elastic_brownout.py`` behind a
hard subprocess timeout.  Run standalone::

    JAX_PLATFORMS=cpu python tools/elastic_smoke.py
"""

from __future__ import annotations

import asyncio
import json
import os
import signal
import sys
import tempfile
import threading
import time

_TOOLS = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(_TOOLS))

BLOCK_SIZE = 8
NUM_BLOCKS = 33
MAX_CONTEXT = 80
GEN_TOKENS = 32
N_REQUESTS = 4


def _engine_config():
    from deepspeed_tpu.inference.v2 import RaggedInferenceEngineConfig

    return RaggedInferenceEngineConfig.from_dict({
        "state_manager": {"max_ragged_batch_size": 32,
                          "max_ragged_sequence_count": 4,
                          "max_context": MAX_CONTEXT},
        "kv_cache": {"block_size": BLOCK_SIZE, "num_blocks": NUM_BLOCKS},
    })


def _scheduler_from_checkpoint(ckpt_dir: str):
    """Rebuild a serving replica from serialized engine state — the same
    factory the elastic scale-up path calls, so a spawned replica is a
    REAL engine restore, not a stub."""
    import jax.numpy as jnp

    from deepspeed_tpu.inference.v2 import InferenceEngineV2
    from deepspeed_tpu.inference.v2.model_implementations import RaggedLlama
    from deepspeed_tpu.models import LlamaConfig
    from deepspeed_tpu.serving import ContinuousBatchScheduler

    cfg = LlamaConfig.tiny(dtype=jnp.float32)
    engine = InferenceEngineV2.load_serialized(
        ckpt_dir, RaggedLlama(cfg, BLOCK_SIZE), _engine_config())
    return ContinuousBatchScheduler(engine)


def run_worker(spool_dir: str, ckpt_dir: str) -> int:
    from deepspeed_tpu.fleet import run_replica_worker

    return run_replica_worker(spool_dir,
                              _scheduler_from_checkpoint(ckpt_dir),
                              flight_flush_every=4)


def _write_checkpoint(base: str) -> str:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from deepspeed_tpu.inference.v2 import InferenceEngineV2
    from deepspeed_tpu.inference.v2.model_implementations import RaggedLlama
    from deepspeed_tpu.models import LlamaConfig, LlamaForCausalLM

    cfg = LlamaConfig.tiny(dtype=jnp.float32)
    params = LlamaForCausalLM(cfg).init(
        jax.random.key(0), np.zeros((1, 4), np.int32))["params"]
    ckpt = os.path.join(base, "engine_ckpt")
    InferenceEngineV2(RaggedLlama(cfg, BLOCK_SIZE), params,
                      _engine_config()).serialize(ckpt)
    return ckpt


def _prompts(seed: int = 0):
    import numpy as np

    rng = np.random.default_rng(seed)
    return [rng.integers(0, 256, size=(int(n),)).tolist()
            for n in rng.integers(8, 16, size=N_REQUESTS)]


def _reference(ckpt: str, prompts, gen: int = GEN_TOKENS):
    """Uninterrupted single-replica run: the greedy-parity oracle."""
    from deepspeed_tpu.serving import SamplingParams

    sched = _scheduler_from_checkpoint(ckpt)
    samp = SamplingParams(greedy=True, max_new_tokens=gen)
    reqs = [sched.submit(p, sampling=samp) for p in prompts]
    sched.run_until_idle()
    assert all(r.state.value == "finished" for r in reqs), \
        [(r.uid, r.state.value, r.finish_reason) for r in reqs]
    return [r.generated for r in reqs]


# --------------------------------------------------------------------- #
# Variant: diurnal soak — the whole elastic loop under shaped traffic
# --------------------------------------------------------------------- #
SOAK_N = 90
SOAK_DURATION_S = 8.0


def run_soak_variant(base: str) -> dict:
    from deepspeed_tpu.fleet import (AdmissionBudget, BrownoutController,
                                     FleetAutoscaler, ServingFleet)
    from deepspeed_tpu.gateway.loadgen import replay, synth_trace
    from deepspeed_tpu.resilience.supervisor import RestartBudget

    ckpt = os.path.join(base, "engine_ckpt")
    # two full day/night swings inside the replay window: the peaks must
    # force scale-ups, the troughs scale-downs — all under open traffic
    trace = synth_trace(
        SOAK_N, seed=1, duration_s=SOAK_DURATION_S,
        prompt_len=(6, 14), max_new_tokens=(4, 8),
    ).shaped(diurnal_depth=0.85, diurnal_period_s=SOAK_DURATION_S / 2)

    # brownout engages BELOW the autoscaler's spawn bar: degradation buys
    # time while real capacity arrives — the paper's brownout ordering
    brownout = BrownoutController(
        ttft_slo_s=0.5, queue_high=80.0, shed_high_per_s=50.0,
        enter_patience=2, exit_patience=2,
        max_transitions=24, transition_window_s=60.0)
    autoscaler = FleetAutoscaler(
        min_replicas=1, max_replicas=3,
        scale_up_backlog=150.0, scale_down_backlog=30.0,
        patience=1, max_moves=16, move_window_s=60.0)
    fleet = ServingFleet(
        lambda name: _scheduler_from_checkpoint(ckpt), replicas=1,
        autoscaler=autoscaler, autoscale_every=2,
        brownout=brownout, brownout_every=2,
        scale_drain_deadline_s=3.0,
        admission=AdmissionBudget(max_backlog_tokens=900.0),
        restart_budget=RestartBudget(max_restarts=64, window_s=60.0))

    timeline = []            # (t, n_replicas, brownout_stage) on change
    max_stage = 0

    def on_tick(now: float) -> None:
        nonlocal max_stage
        sample = (len(fleet.router.replicas), brownout.stage)
        max_stage = max(max_stage, brownout.stage)
        if not timeline or timeline[-1][1:] != sample:
            timeline.append((round(now, 2), *sample))

    report = replay(trace, fleet, speed=1.0, vocab=256, greedy=True,
                    max_wall_s=150.0, drain=True, on_tick=on_tick)
    fleet.run_until_idle(max_ticks=4000)
    # the trace is over: a final graceful downsize back to 1 replica
    # (idle victims, instant drains), then let the ladder fully disengage
    fleet.set_replica_count(1, drain_deadline_s=3.0)
    for _ in range(100):
        if brownout.stage == 0:
            break
        fleet.step()

    snap = fleet.snapshot()
    ups, downs = snap["fleet/scale_ups"], snap["fleet/scale_downs"]
    assert ups >= 1.0 and downs >= 1.0, \
        f"diurnal soak never scaled (ups={ups} downs={downs}): {timeline}"
    assert max_stage >= 1, \
        f"brownout never engaged under the peak: {timeline}"
    assert brownout.stage == 0, \
        f"brownout did not disengage after the peak: stage={brownout.stage}"
    # zero lost: every admitted request FINISHED (sheds happened at the
    # admission door, with retry hints — those are not losses)
    assert report["failed"] == 0, report
    unfinished = [fr for fr in fleet.requests if not fr.done]
    assert not unfinished, [(fr.uid, fr.state) for fr in unfinished]
    # healthy downsizes migrate by handoff — NOTHING crash-replays
    assert all(fr.replays == 0 for fr in fleet.requests), \
        [(fr.uid, fr.replays) for fr in fleet.requests if fr.replays]
    # interactive is protected at every stage below 5 (and stage 5's
    # standard squeeze never fired here unless the ladder topped out)
    inter_sheds = report["sheds_by_class"].get("interactive", 0)
    assert max_stage >= 5 or inter_sheds == 0, \
        (max_stage, report["sheds_by_class"])
    handoffs = sum(fr.handoffs for fr in fleet.requests)
    return {
        "soak_requests": report["requests"],
        "soak_submitted": report["submitted"],
        "soak_finished": report["finished"],
        "soak_scale_ups": int(ups),
        "soak_scale_downs": int(downs),
        "soak_brownout_max_stage": max_stage,
        "soak_brownout_transitions": brownout.transitions,
        "soak_sheds_by_class": report["sheds_by_class"],
        "soak_handoffs": handoffs,
        "soak_goodput_tokens_per_s": report["goodput_tokens_per_s"],
        "soak_interactive_p95_ttft_s": report["classes"].get(
            "interactive", {}).get("p95_ttft_s"),
        "soak_spawn_s": snap.get("fleet/scale_up_spawn_s"),
        "soak_drain_s": snap.get("fleet/scale_down_drain_s"),
        "soak_timeline": timeline[:24],
    }


# --------------------------------------------------------------------- #
# Variant: live SSE streams survive forced scale events
# --------------------------------------------------------------------- #
STREAM_GEN = 60


def run_stream_variant(base: str, gold_stream) -> dict:
    from deepspeed_tpu.fleet import ServingFleet
    from deepspeed_tpu.gateway.client import generate
    from deepspeed_tpu.gateway.server import GatewayServer

    ckpt = os.path.join(base, "engine_ckpt")
    prompts = _prompts()[:3]
    fleet = ServingFleet(lambda name: _scheduler_from_checkpoint(ckpt),
                         replicas=2)

    async def _drive():
        gw = GatewayServer(fleet, max_stream_s=180.0)
        await gw.start()
        first = asyncio.Event()

        def on_event(ev, data):
            if ev == "token":
                first.set()

        try:
            tasks = [asyncio.ensure_future(generate(
                "127.0.0.1", gw.port, p, max_new_tokens=STREAM_GEN,
                priority_class="interactive", on_event=on_event,
                timeout_s=180.0)) for p in prompts]
            # tokens are flowing: force a scale-up, then a double
            # scale-down with a ZERO drain deadline so in-flight streams
            # take the handoff path mid-generation instead of finishing
            # on the victim (warm CPU decode outruns any real deadline)
            await asyncio.wait_for(first.wait(), 90.0)
            fleet.set_replica_count(3)
            fleet.set_replica_count(1, drain_deadline_s=0.0)
            resps = await asyncio.gather(*tasks)
        finally:
            await gw.stop()
        return gw, resps

    gw, resps = asyncio.run(_drive())
    for i, resp in enumerate(resps):
        assert resp.status == 200, (resp.status, resp.body)
        ev, data = resp.terminal
        assert ev == "done", (i, resp.terminal)
        assert resp.positions == list(range(len(resp.tokens))), \
            f"stream {i} has position gaps: {resp.positions}"
        assert resp.tokens == gold_stream[i], \
            f"stream {i} diverged across the scale events"
    assert gw.metrics.duplicates_suppressed == 0
    snap = fleet.snapshot()
    assert snap["fleet/scale_ups"] >= 1.0, snap
    assert snap["fleet/scale_downs"] == 2.0, snap
    assert all(fr.replays == 0 for fr in fleet.requests), \
        "a graceful downsize replayed a stream"
    handoffs = sum(fr.handoffs for fr in fleet.requests)
    assert handoffs >= 1, \
        "no stream migrated mid-generation — shorten the drain deadline"
    return {
        "streams": len(resps),
        "streams_handoffs": handoffs,
        "streams_drain_s": snap.get("fleet/scale_down_drain_s"),
    }


# --------------------------------------------------------------------- #
# Variant: spawn_fail — capacity cannot arrive, brownout goes deeper
# --------------------------------------------------------------------- #
SPAWN_FAIL_REQUESTS = 16


def run_spawn_fail_brownout_variant(base: str, gold) -> dict:
    from deepspeed_tpu.fleet import (AdmissionBudget, BrownoutController,
                                     FleetAutoscaler, ServingFleet)
    from deepspeed_tpu.resilience import chaos
    from deepspeed_tpu.resilience.supervisor import RestartBudget
    from deepspeed_tpu.serving import SamplingParams

    ckpt = os.path.join(base, "engine_ckpt")
    prompts = _prompts()
    samp = SamplingParams(greedy=True, max_new_tokens=GEN_TOKENS)
    # queue pressure drives the ladder deterministically (the TTFT and
    # shed bars sit far away); the backlog of 16 queued requests on one
    # replica is ~7x the queue_high bar
    brownout = BrownoutController(
        ttft_slo_s=60.0, queue_high=60.0, shed_high_per_s=1e6,
        enter_patience=1, exit_patience=2,
        max_transitions=20, transition_window_s=60.0)
    autoscaler = FleetAutoscaler(
        min_replicas=1, max_replicas=3,
        scale_up_backlog=40.0, scale_down_backlog=8.0,
        patience=1, max_moves=8, move_window_s=60.0)
    fleet = ServingFleet(
        lambda name: _scheduler_from_checkpoint(ckpt), replicas=1,
        autoscaler=autoscaler, autoscale_every=2,
        brownout=brownout, brownout_every=2,
        breaker_kwargs={"failure_threshold": 2, "cooloff_s": 30.0},
        admission=AdmissionBudget(max_backlog_tokens=4000.0),
        restart_budget=RestartBudget(max_restarts=16, window_s=60.0))

    chaos.arm("spawn_fail", "raise", count=0)
    max_stage = 0
    try:
        frs = [fleet.submit(prompts[i % len(prompts)], sampling=samp)
               for i in range(SPAWN_FAIL_REQUESTS)]
        ticks = 0
        while fleet.num_pending and ticks < 6000:
            fleet.step()
            max_stage = max(max_stage, brownout.stage)
            ticks += 1
    finally:
        chaos.disarm("spawn_fail")
    snap = fleet.snapshot()
    # the scale-up attempts FAILED (and kept failing), visibly
    assert snap["fleet/scale_spawn_failed"] >= 2.0, snap
    assert fleet.scale_breaker.opens >= 1, \
        f"scale breaker never opened: {fleet.scale_breaker.failures} fails"
    assert len(fleet.router.replicas) == 1, \
        "a spawn somehow succeeded under spawn_fail chaos"
    # ... so the ladder went deeper instead of the fleet crashing
    assert max_stage >= 2, f"brownout stayed shallow: {max_stage}"
    # zero losses, greedy-exact — degraded quality never corrupts streams
    for i, fr in enumerate(frs):
        assert fr.state == "finished", (fr.uid, fr.state, fr.finish_reason)
        assert fr.tokens == gold[i % len(gold)], \
            f"request {fr.uid} diverged under brownout"
    # backlog gone: the ladder must fully let go (reverse order)
    for _ in range(100):
        if brownout.stage == 0:
            break
        fleet.step()
    assert brownout.stage == 0, brownout.stage
    return {
        "spawn_fail_scale_attempts": int(snap["fleet/scale_spawn_failed"]),
        "spawn_fail_breaker_opens": fleet.scale_breaker.opens,
        "spawn_fail_brownout_max_stage": max_stage,
        "spawn_fail_brownout_transitions": brownout.transitions,
    }


# --------------------------------------------------------------------- #
# Variant: subprocess workers — real spawn/teardown, SIGKILL mid-drain,
# and the deadline-through-gateway satellite regression
# --------------------------------------------------------------------- #
DEADLINE_GEN = 60
#: well under what 60 tokens take on any host: since the decode tick
#: dispatches ahead (PR 28) and walks only held blocks (PR 30) an idle
#: sandbox decodes this model at ~1.7 ms a token, 60 tokens in ~0.11 s,
#: and a deadline of 0.15 s expired only while other tests loaded the host
DEADLINE_S = 0.05


def run_subprocess_variant(base: str, gold) -> dict:
    from deepspeed_tpu.fleet import FleetFrontEnd
    from deepspeed_tpu.fleet.worker import STOP_FILE
    from deepspeed_tpu.resilience.supervisor import BackoffPolicy
    from deepspeed_tpu.serving import SamplingParams

    ckpt = os.path.join(base, "engine_ckpt")
    prompts = _prompts()

    def worker_argv(name, spool):
        return [sys.executable, os.path.abspath(__file__), "--worker",
                spool, ckpt]

    fe = FleetFrontEnd(
        worker_argv, 2, os.path.join(base, "elastic"),
        heartbeat_interval_s=2.0,
        hang_timeout_s=90.0,
        backoff=BackoffPolicy(base_s=0.2, jitter=0.0),
        max_restarts=3,
        env={"JAX_PLATFORMS": "cpu"})
    try:
        samp = SamplingParams(greedy=True, max_new_tokens=GEN_TOKENS)
        frs = [fe.submit(p, sampling=samp) for p in prompts]
        # wait until the initial workers are actually serving
        deadline = time.monotonic() + 180
        while time.monotonic() < deadline:
            fe.poll()
            if any(fr.tokens for fr in frs):
                break
            time.sleep(0.01)
        assert any(fr.tokens for fr in frs), "initial workers never served"

        # -- scale-up #1: latency from the add_worker call to the first
        # token a request serves AFTER capacity arrived ----------------- #
        t_add = time.monotonic()
        fe.add_worker()
        probe = fe.submit(prompts[0], sampling=samp)
        t_first = None
        deadline = time.monotonic() + 180
        while time.monotonic() < deadline:
            fe.poll()
            if probe.tokens:
                t_first = time.monotonic()
                break
            time.sleep(0.01)
        assert t_first is not None, "post-scale-up probe never served"
        scale_up_first_token_s = t_first - t_add

        # -- scale-down #1: GRACEFUL — generous drain deadline, victim
        # finishes its own in-flight work, zero replays ----------------- #
        busy = [fr for fr in [*frs, probe] if not fr.done]
        victims = {fr.replica for fr in busy if fr.replica is not None}
        victims.discard(probe.replica)
        graceful = (sorted(victims)[0] if victims
                    else sorted(set(fe.spools) - {probe.replica})[0])
        t0 = time.monotonic()
        migrated = fe.remove_worker(graceful, drain_deadline_s=120.0)
        graceful_drain_s = time.monotonic() - t0
        assert fe.drain_escalations == 0, \
            "a generous graceful drain escalated"
        assert fe.replays == 0, \
            f"graceful downsize replayed {fe.replays} request(s)"
        assert migrated == 0, \
            f"graceful drain left {migrated} request(s) to migrate"

        # -- scale-up #2 + scale-down #2: SIGKILL the draining victim —
        # the journal replays its leftovers, zero requests lost --------- #
        frs2 = [fe.submit(p, sampling=samp) for p in prompts]
        fe.add_worker()
        victim = None
        deadline = time.monotonic() + 180
        while time.monotonic() < deadline:
            fe.poll()
            cands = [fr for fr in frs2
                     if not fr.done and fr.replica is not None
                     and 1 <= len(fr.tokens) <= GEN_TOKENS // 2]
            routable = len(fe.spools) - len(getattr(fe, "_retiring", ()))
            if cands and routable > 1:
                victim = cands[0].replica
                break
            time.sleep(0.01)
        assert victim is not None, "never observed a mid-decode request"
        sup = fe.supervisors[victim]
        stop_path = os.path.join(fe.spools[victim], STOP_FILE)
        pid = sup.handles[0].pid
        th = threading.Thread(target=fe.remove_worker, args=(victim,),
                              kwargs={"drain_deadline_s": 90.0})
        th.start()
        # the stop file marks drain start — SIGKILL the victim mid-drain
        deadline = time.monotonic() + 30
        while not os.path.exists(stop_path) \
                and time.monotonic() < deadline:
            time.sleep(0.001)
        assert os.path.exists(stop_path), "drain never started"
        os.kill(pid, signal.SIGKILL)
        th.join(timeout=150)
        assert not th.is_alive(), "remove_worker hung after SIGKILL"
        assert fe.replays >= 1, \
            "SIGKILL mid-drain produced no journal replay"

        fe.run_until_idle(timeout_s=240)
        assert fe.num_pending == 0, [
            (fr.uid, fr.state, fr.replica) for fr in fe.requests.values()
            if not fr.done]
        for i, fr in enumerate([*frs, *frs2]):
            assert fr.state == "finished", \
                (fr.uid, fr.state, fr.finish_reason)
            assert fr.tokens == gold[i % len(gold)], \
                f"request {fr.uid} diverged (replays={fr.replays})"
        assert probe.state == "finished" and probe.tokens == gold[0]
        assert fe.scale_ups == 2 and fe.scale_downs == 2, \
            (fe.scale_ups, fe.scale_downs)

        # -- satellite: a deadline that expires ON a subprocess worker
        # surfaces through the gateway as a TYPED deadline SSE error ----- #
        from deepspeed_tpu.gateway.client import generate
        from deepspeed_tpu.gateway.server import GatewayServer

        async def _deadline_probe():
            gw = GatewayServer(fe, max_stream_s=120.0)
            await gw.start()
            try:
                return await generate(
                    "127.0.0.1", gw.port, list(range(8)),
                    max_new_tokens=DEADLINE_GEN, deadline_s=DEADLINE_S,
                    timeout_s=120.0), gw.metrics.deadline_expired
            finally:
                await gw.stop()

        resp, expired = asyncio.run(_deadline_probe())
        ev, data = resp.terminal
        assert ev == "error" and data["type"] == "deadline", resp.events
        assert expired == 1

        return {
            "subprocess_scale_up_first_token_s":
                round(scale_up_first_token_s, 3),
            "subprocess_graceful_drain_s": round(graceful_drain_s, 3),
            "subprocess_graceful_migrated": migrated,
            "subprocess_kill_replays": fe.replays,
            "subprocess_drain_escalations": fe.drain_escalations,
            "subprocess_scale_ups": fe.scale_ups,
            "subprocess_scale_downs": fe.scale_downs,
        }
    finally:
        fe.stop(timeout_s=60)


def run_smoke(tmpdir: str | None = None) -> dict:
    if tmpdir is None:
        tmpdir = tempfile.mkdtemp(prefix="elastic_smoke_")
    ckpt = _write_checkpoint(tmpdir)
    prompts = _prompts()
    gold = _reference(ckpt, prompts)
    gold_stream = _reference(ckpt, prompts[:3], gen=STREAM_GEN)
    snap = {}
    snap.update(run_soak_variant(tmpdir))
    snap.update(run_stream_variant(tmpdir, gold_stream))
    snap.update(run_spawn_fail_brownout_variant(tmpdir, gold))
    snap.update(run_subprocess_variant(tmpdir, gold))
    return snap


def main() -> int:
    if len(sys.argv) > 1 and sys.argv[1] == "--worker":
        return run_worker(sys.argv[2], sys.argv[3])
    t0 = time.monotonic()
    snap = run_smoke()
    snap["wall_s"] = round(time.monotonic() - t0, 2)
    print(json.dumps({"elastic_smoke": "ok", **snap}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
