"""HTTP/SSE gateway + recorded-trace load harness: the StreamBridge's
exactly-once ``(uid, position)`` contract under replayed/duplicated
callbacks and a real kill→journal-replay mid-stream; edge-minted
``trace_id`` continuity (HTTP response header → one connected,
obs_dump-valid trace spanning the gateway accept span, the scheduler's
request spans, and the emitting tick); the ``gateway/*`` metric
namespace under metrics_lint; the trace recorder/shaper/replayer; and
the subprocess smoke (``tools/gateway_smoke.py``) behind a hard
timeout.
"""

import asyncio
import importlib.util
import json
import os
import pathlib
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.fleet import ServingFleet
from deepspeed_tpu.gateway import (GatewayServer, RequestTrace,
                                   StreamBridge, TraceRequest, generate,
                                   synth_trace)
from deepspeed_tpu.inference.v2 import (InferenceEngineV2,
                                        RaggedInferenceEngineConfig)
from deepspeed_tpu.inference.v2.model_implementations import RaggedLlama
from deepspeed_tpu.models import LlamaConfig, LlamaForCausalLM
from deepspeed_tpu.serving import ContinuousBatchScheduler, SamplingParams

CFG = LlamaConfig.tiny(dtype=jnp.float32)
_TOOLS = pathlib.Path(__file__).resolve().parents[2] / "tools"
_TOOL = _TOOLS / "gateway_smoke.py"
GEN = 5


def _load_tool(name):
    spec = importlib.util.spec_from_file_location(name,
                                                  _TOOLS / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def params():
    return LlamaForCausalLM(CFG).init(
        jax.random.key(0), np.zeros((1, 4), np.int32))["params"]


def _sched(params, num_blocks=17):
    cfg = RaggedInferenceEngineConfig.from_dict({
        "state_manager": {"max_ragged_batch_size": 32,
                          "max_ragged_sequence_count": 4,
                          "max_context": 48},
        "kv_cache": {"block_size": 8, "num_blocks": num_blocks},
    })
    return ContinuousBatchScheduler(
        InferenceEngineV2(RaggedLlama(CFG, 8), params, cfg))


def _prompts(n=3, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, CFG.vocab_size, size=(int(k),)).tolist()
            for k in rng.integers(8, 16, size=n)]


# --------------------------------------------------------------------- #
# StreamBridge: exactly-once by (uid, position), pure unit level
# --------------------------------------------------------------------- #
class _FakeReq:
    def __init__(self, uid=7):
        self.uid = uid
        self.tokens = []


def test_bridge_suppresses_duplicate_callbacks():
    """A replay path that re-fires on_token for already-journaled
    positions must not re-emit them on the wire."""
    req = _FakeReq()
    b = StreamBridge()
    req.tokens.append(11)
    b.on_token(req, 11)
    req.tokens.append(22)
    b.on_token(req, 22)
    # pathological re-fire of BOTH delivered positions (journal
    # unchanged): suppressed, never re-emitted
    b.on_token(req, 11)
    b.on_token(req, 22)
    assert b.duplicates_suppressed == 2
    req.tokens.append(33)
    b.on_token(req, 33)
    assert b.drain() == [(0, 11), (1, 22), (2, 33)]
    assert b.emitted == [11, 22, 33]
    assert b.uid == 7 and b.pending == 0


def test_bridge_catches_up_on_skipped_callbacks():
    """A burst of journal appends delivered under ONE callback (e.g.
    speculative acceptances) emits every position, in order."""
    req = _FakeReq()
    b = StreamBridge()
    req.tokens.extend([1, 2, 3])
    b.on_token(req, 3)
    assert b.drain() == [(0, 1), (1, 2), (2, 3)]
    assert b.duplicates_suppressed == 0


# --------------------------------------------------------------------- #
# Exactly-once across a real failure: kill -> journal replay mid-stream
# --------------------------------------------------------------------- #
def test_sse_stream_exactly_once_across_kill_replay(params):
    """Kill the serving replica after the first tokens of an SSE stream:
    the journal replay must continue the stream gap-free and
    duplicate-free, byte-identical to an undisturbed greedy run."""
    sched = _sched(params)
    prompt = _prompts(n=1, seed=4)[0]
    gen = 12
    ref = sched.submit(prompt, sampling=SamplingParams(
        greedy=True, max_new_tokens=gen))
    sched.run_until_idle(max_ticks=500)
    gold = list(ref.generated)

    fleet = ServingFleet(lambda name: _sched(params), replicas=2)
    gw = GatewayServer(fleet, max_stream_s=120.0)
    killed = []

    async def _killer():
        # watch the fleet's own journal and kill the serving replica
        # once the stream is demonstrably mid-flight (>= 3 tokens
        # delivered, request still live)
        while True:
            frs = fleet.requests
            if frs:
                fr = frs[0]
                if fr.done:
                    return
                if len(fr.tokens) >= 3:
                    killed.append(fleet.kill_replica(fr.replica))
                    return
            await asyncio.sleep(0.001)

    async def _drive():
        await gw.start()
        try:
            resp, _ = await asyncio.gather(
                generate("127.0.0.1", gw.port, prompt,
                         max_new_tokens=gen, timeout_s=120.0),
                _killer())
            return resp
        finally:
            await gw.stop()

    resp = asyncio.run(_drive())
    assert killed == [1], "the kill must have caught the request in flight"
    fr = fleet.requests[0]
    assert fr.replays == 1 and len(fr.replicas) == 2
    assert resp.terminal[0] == "done", resp.terminal
    assert resp.tokens == gold, "replayed stream diverged from gold"
    assert resp.positions == list(range(len(gold))), \
        f"positions not gap-free/duplicate-free: {resp.positions}"
    assert gw.metrics.duplicates_suppressed == 0, \
        "healthy replay re-fired delivered positions at the bridge"


# --------------------------------------------------------------------- #
# Edge-minted trace id: one connected trace, HTTP accept -> tick -> emit
# --------------------------------------------------------------------- #
def test_trace_id_header_resolves_to_connected_trace(params):
    obs_dump = _load_tool("obs_dump")
    fleet = ServingFleet(lambda name: _sched(params), replicas=2)
    gw = GatewayServer(fleet)
    prompts = _prompts(n=2, seed=9)

    async def _drive():
        await gw.start()
        try:
            return await asyncio.gather(*[
                generate("127.0.0.1", gw.port, p, max_new_tokens=GEN)
                for p in prompts])
        finally:
            await gw.stop()

    resps = asyncio.run(_drive())
    events = [e for e in fleet.tracer.export_events()
              if e.get("ph") != "M"]
    assert obs_dump.validate_trace(events) == []
    emits = [e for e in events if e["name"] == "tick"
             and (e.get("args") or {}).get("emitted")]
    assert emits, "no scheduler tick span recorded emitted tokens"
    for resp in resps:
        assert resp.status == 200 and resp.trace_id
        assert resp.trace_id == resp.terminal[1]["trace_id"]
        mine = [e for e in events
                if (e.get("args") or {}).get("trace_id") == resp.trace_id]
        by_name = {}
        for e in mine:
            by_name.setdefault(e["name"], []).append(e)
        # the edge span and the scheduler's request spans share the id
        assert "http/request" in by_name, sorted(by_name)
        assert "request/submit" in by_name, sorted(by_name)
        decode = by_name.get("request/decode") \
            or by_name.get("request/prefill")
        assert decode, sorted(by_name)
        # connected in TIME too: the gateway accept span covers the
        # request's decode work, and some scheduler tick emitted a token
        # inside that window — accept -> tick -> emit on one timeline
        g = by_name["http/request"][0]
        g0, g1 = g["ts"], g["ts"] + g.get("dur", 0.0)
        d = decode[0]
        assert g0 <= d["ts"] <= g1, (g0, d["ts"], g1)
        assert any(g0 <= e["ts"] <= g1 for e in emits), \
            "no emitting tick inside the gateway accept span"
        # uid attr ties the edge span to the scheduler request
        uid = int(resp.headers["x-request-uid"])
        sub = (by_name["request/submit"][0].get("args") or {})
        assert int(sub.get("uid", -1)) == uid


# --------------------------------------------------------------------- #
# gateway/* namespace rides the metric-name lint like every other layer
# --------------------------------------------------------------------- #
def test_metrics_lint_covers_gateway_namespace(tmp_path):
    from deepspeed_tpu.analysis.metrics_lint import (declared_specs,
                                                     run_metrics_lint)

    names = {s.name for s in declared_specs()}
    assert "gateway/streams_finished" in names
    assert "gateway/sheds_429" in names

    src = textwrap.dedent("""
        def export(m, k):
            m.write("gateway/strems_started", 1)   # typo'd exact name
            m.write("gateway/streams_started", 2)  # declared: clean
            m.write(f"gateway/p95_{k}", 3)         # declared family: clean
            m.write(f"gateway/rplay_{k}", 4)       # typo'd family prefix
    """)
    p = tmp_path / "m.py"
    p.write_text(src)
    findings = run_metrics_lint([str(p)])
    assert len(findings) == 2, findings
    msgs = " | ".join(f.message for f in findings)
    assert "gateway/strems_started" in msgs and "gateway/rplay_" in msgs


# --------------------------------------------------------------------- #
# Trace recorder / shaper / replayer (no model: pure trace mechanics)
# --------------------------------------------------------------------- #
def test_trace_jsonl_round_trip(tmp_path):
    t = synth_trace(24, seed=5, duration_s=2.0)
    path = str(tmp_path / "trace.jsonl")
    t.dump(path)
    t2 = RequestTrace.load(path)
    assert len(t2) == 24
    assert [r.to_json() for r in t.requests] \
        == [r.to_json() for r in t2.requests]
    assert t2.meta["source"] == "synth" and t2.meta["seed"] == 5
    # multi-tenant, multi-class, with session reuse
    assert len({r.tenant for r in t2.requests}) == 2
    assert len({r.priority_class for r in t2.requests}) >= 2
    sessions = [r.session for r in t2.requests if r.session]
    assert len(sessions) > len(set(sessions)), "no session reuse recorded"


def test_trace_load_rejects_foreign_jsonl(tmp_path):
    p = tmp_path / "not_a_trace.jsonl"
    p.write_text('{"some": "header"}\n{"offset_s": 0.0}\n')
    with pytest.raises(ValueError, match="not a gateway trace"):
        RequestTrace.load(str(p))


def test_trace_shaping_load_burst_diurnal():
    t = synth_trace(60, seed=1, duration_s=4.0)
    # load scaling compresses offsets linearly
    fast = t.shaped(load=2.0)
    assert abs(fast.duration_s - t.duration_s / 2) < 1e-6
    # burst shaping keeps each arrival in its period but packs it into
    # the period's head — same mean rate, bursty delivery
    burst = t.shaped(burst_factor=4.0, burst_period_s=1.0)
    assert len(burst) == len(t)
    for r in burst.requests:
        assert (r.offset_s % 1.0) <= 0.25 + 1e-6, r.offset_s
    # diurnal warp is deterministic, monotone (order-preserving), and
    # actually moves density: offsets cluster toward the sine troughs
    d1 = t.shaped(diurnal_depth=0.8, diurnal_period_s=2.0)
    d2 = t.shaped(diurnal_depth=0.8, diurnal_period_s=2.0)
    offs = [r.offset_s for r in d1.requests]
    assert offs == [r.offset_s for r in d2.requests]
    assert offs == sorted(offs)
    assert offs != [r.offset_s for r in t.requests]
    with pytest.raises(ValueError, match="diurnal_depth"):
        t.shaped(diurnal_depth=1.5, diurnal_period_s=2.0)


def test_record_fleet_and_replay_round_trip(params):
    """Record a live fleet run, then replay the trace open-loop against
    a fresh fleet: every class/tenant/length survives the round trip and
    the report carries per-class latency percentiles."""
    from deepspeed_tpu.gateway import loadgen

    fleet = ServingFleet(lambda name: _sched(params), replicas=1)
    samp = SamplingParams(greedy=True, max_new_tokens=GEN)
    for i, p in enumerate(_prompts(n=3, seed=2)):
        fleet.submit(p, tenant=f"t{i % 2}",
                     priority_class=["interactive", "batch"][i % 2],
                     sampling=samp)
        fleet.step()
    trace = RequestTrace.record_fleet(fleet)
    fleet.run_until_idle(max_ticks=500)

    assert len(trace) == 3 and trace.meta["source"] == "fleet"
    assert trace.requests[0].offset_s == 0.0
    assert {r.tenant for r in trace.requests} == {"t0", "t1"}
    assert {r.priority_class for r in trace.requests} \
        == {"interactive", "batch"}
    assert all(r.max_new_tokens == GEN for r in trace.requests)

    replayer = ServingFleet(lambda name: _sched(params), replicas=1)
    report = loadgen.replay(trace, replayer, vocab=CFG.vocab_size,
                            speed=4.0, max_wall_s=60.0)
    assert report["submitted"] == 3 and report["finished"] == 3
    assert report["shed_total"] == 0 and report["failed"] == 0
    assert report["goodput_tokens_per_s"] > 0
    for cls in ("interactive", "batch"):
        assert report["classes"][cls]["finished"] >= 1
        assert "p50_ttft_s" in report["classes"][cls]


# --------------------------------------------------------------------- #
# The tier-1 smoke: real sockets, 8 concurrent SSE streams, forced 429
# with Retry-After, deadline expiry mid-stream, greedy parity, and the
# 2x recorded-burst replay — behind a HARD timeout.
# --------------------------------------------------------------------- #
def test_gateway_smoke_tool():
    proc = subprocess.run(
        [sys.executable, str(_TOOL)],
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
        capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, \
        f"stdout:\n{proc.stdout[-3000:]}\nstderr:\n{proc.stderr[-3000:]}"
    lines = [ln for ln in proc.stdout.splitlines()
             if ln.startswith('{"gateway_smoke"')]
    assert lines, proc.stdout[-2000:]
    snap = json.loads(lines[-1])
    assert snap["gateway_smoke"] == "ok"
    assert snap["streams"] == 8
    assert snap["stream_parity"] == "greedy-exact"
    assert snap["trace_ids_distinct"] == 8
    assert snap["trace_problems"] == 0
    assert snap["duplicates_suppressed"] == 0
    assert snap["deadline_error_type"] == "deadline"
    assert snap["shed_retry_after_s"] >= 1
    assert snap["shed_class"] == "batch"
    assert snap["quota_429"] == "quota"
    # the 2x recorded-burst replay: batch-first shedding, interactive
    # fully protected, goodput measured
    assert snap["replay_shed_batch"] > 0
    assert snap["replay_shed_interactive"] == 0
    assert snap["replay_finished"] > 0
    assert snap["replay_goodput_tokens_per_s"] > 0
