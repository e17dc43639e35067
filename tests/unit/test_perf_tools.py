"""The HLO memory ledger (compile-time evidence + explicit
unavailability), live occupancy gauges (TraceGuard-clean), and
obs_dump's flight-ring validation."""

import importlib.util
import json
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference.v2 import (InferenceEngineV2,
                                        RaggedInferenceEngineConfig)
from deepspeed_tpu.inference.v2.model_implementations import RaggedLlama
from deepspeed_tpu.models import LlamaConfig, LlamaForCausalLM
from deepspeed_tpu.observability import (MemoryLedger, MetricsRegistry,
                                         Tracer, kv_occupancy,
                                         mint_trace_id, tenant_occupancy)
from deepspeed_tpu.observability.memory import tree_bytes
from deepspeed_tpu.serving import (ContinuousBatchScheduler, RequestState,
                                   SamplingParams)

CFG = LlamaConfig.tiny(dtype=jnp.float32)
_TOOLS = pathlib.Path(__file__).resolve().parents[2] / "tools"


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


def _sched(params, tracer=None, registry=None, num_blocks=17,
           max_context=64):
    cfg = RaggedInferenceEngineConfig.from_dict({
        "state_manager": {"max_ragged_batch_size": 32,
                          "max_ragged_sequence_count": 4,
                          "max_context": max_context},
        "kv_cache": {"block_size": 8, "num_blocks": num_blocks},
    })
    return ContinuousBatchScheduler(
        InferenceEngineV2(RaggedLlama(CFG, 8), params, cfg),
        tracer=tracer, registry=registry)


# --------------------------------------------------------------------- #
# Memory ledger
# --------------------------------------------------------------------- #
def test_ledger_capture_lowering_and_roundtrip():
    led = MemoryLedger()
    a = jax.ShapeDtypeStruct((128, 128), jnp.float32)
    entry = led.capture_lowering("matmul", lambda x: x @ x, a)
    assert entry["memory"]["available"] is True
    assert entry["memory"]["argument_size_in_bytes"] == 128 * 128 * 4
    assert entry["cost"]["flops"] >= 2 * 128 ** 3
    led.record_unavailable("missing", "backend omits analysis",
                           meta={"why": "test"})
    data = led.to_json()
    back = MemoryLedger.from_json(json.loads(json.dumps(data)))
    assert back.entries["missing"]["memory"]["available"] is False
    assert "backend omits" in back.entries["missing"]["memory"]["reason"]
    # a failing lowering becomes an explicit record, never a raise
    bad = led.capture_lowering("broken", lambda x: x @ jnp.ones((3, 3)), a)
    assert bad["memory"]["available"] is False
    # telemetry names are declared observability/hbm_* family members
    reg = MetricsRegistry.default()
    for name in led.telemetry():
        assert reg.lookup(name) is not None, name


def test_engine_v2_memory_ledger_and_occupancy(params):
    sched = _sched(params)
    engine = sched.engine
    led = engine.capture_memory_ledger()
    mem = led.entries["decode_step"]["memory"]
    if mem.get("available"):
        # the KV pool is carried in (donated) arguments: 17 blocks * 8
        # rows of K+V across layers must be visible in argument bytes
        kv_bytes = tree_bytes(engine.state_manager.kv_cache.cache)
        assert mem["argument_size_in_bytes"] >= kv_bytes
    else:
        assert mem["reason"]
    # occupancy: host-side bookkeeping in lockstep with the allocator
    occ = kv_occupancy(engine.state_manager)
    assert occ["observability/kv_blocks_total"] == 16.0   # 17 - trash
    assert occ["observability/kv_blocks_free"] == 16.0
    rng = np.random.default_rng(1)
    reqs = [sched.submit(rng.integers(0, CFG.vocab_size,
                                      size=(12,)).tolist(),
                         sampling=SamplingParams(greedy=True,
                                                 max_new_tokens=4))
            for _ in range(2)]
    for _ in range(3):
        sched.step()
    occ = kv_occupancy(engine.state_manager)
    alloc = engine.state_manager.allocator
    assert occ["observability/kv_blocks_free"] == float(alloc.free_blocks)
    assert occ["observability/kv_blocks_live"] == float(
        16 - alloc.free_blocks) > 0
    assert occ["observability/kv_tokens_live"] > 0
    assert occ["observability/kv_sequences_live"] == 2.0
    # per-tenant occupancy: live token history, keyed by request.tenant
    reqs[0].tenant = "acme"
    live = list(sched._running.values())
    ten = tenant_occupancy(live)
    assert ten["observability/tenant_tokens_acme"] == float(
        len(reqs[0].history))
    sched.run_until_idle()


def test_occupancy_gauges_traceguard_clean(params):
    """Acceptance: live gauges read host-side state only — a registry
    scrape per steady-state decode tick adds 0 compiles and 0 host
    syncs vs the gauge-free tick."""
    from deepspeed_tpu.analysis.trace_guard import TraceGuard

    def run(with_registry):
        reg = MetricsRegistry() if with_registry else None
        sched = _sched(params, registry=reg, num_blocks=33,
                       max_context=64)
        rng = np.random.default_rng(2)
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
            sched.step()                      # warm the decode programs
        with TraceGuard(max_compiles=0, d2h="disallow",
                        label="decode tick + gauges") as tg:
            for _ in range(4):
                assert sched.step()
                if reg is not None:
                    snap = reg.snapshot()
                    assert snap["observability/kv_blocks_live"] > 0
        if reg is not None:
            assert not reg.unknown_names, reg.unknown_names
        sched.run_until_idle()
        return tg

    bare = run(False)
    gauged = run(True)
    assert gauged.compiles == 0
    assert gauged.host_syncs == bare.host_syncs


# --------------------------------------------------------------------- #
# obs_dump flight validation
# --------------------------------------------------------------------- #
def test_validate_flight_good_ring(tmp_path):
    obs_dump = _load_tool("obs_dump")
    from deepspeed_tpu.observability import FlightRecorder

    tr = Tracer(tid="replica0#2")
    t = mint_trace_id()
    for i in range(4):
        with tr.span(f"tick{i}", trace_id=t):
            pass
    fl = str(tmp_path / "flight.2.json")
    rec = FlightRecorder(tr, fl, flush_every=1)
    rec.tick()
    assert obs_dump.validate_flight(fl) == []


def test_validate_flight_fails_loudly(tmp_path):
    obs_dump = _load_tool("obs_dump")
    # torn JSON (SIGKILL mid-write without the atomic rename)
    torn = tmp_path / "flight.0.json"
    torn.write_text('{"schema": "ds-flight-v1", "spans": [')
    assert any("torn" in p for p in obs_dump.validate_flight(str(torn)))
    # wrong schema
    bad = tmp_path / "flight.1.json"
    bad.write_text(json.dumps({"schema": "nope", "spans": []}))
    assert any("ds-flight-v1" in p
               for p in obs_dump.validate_flight(str(bad)))
    # incarnation tag does not match the attempt suffix
    tr = Tracer(tid="replica0#3")
    with tr.span("tick", trace_id="t"):
        pass
    from deepspeed_tpu.observability import FlightRecorder

    fl = tmp_path / "flight.1.json"
    FlightRecorder(tr, str(fl), flush_every=1).tick()
    probs = obs_dump.validate_flight(str(fl))
    assert any("incarnation tag" in p for p in probs), probs
    # ring order broken (a doctored file: finish timestamps regress)
    payload = json.loads(fl.read_text())
    payload["spans"] = [
        {"name": "a", "ph": "X", "ts": 100.0, "dur": 1.0, "tid": "w#1",
         "args": {"trace_id": "t", "span_id": "s1"}},
        {"name": "b", "ph": "X", "ts": 10.0, "dur": 1.0, "tid": "w#1",
         "args": {"trace_id": "t", "span_id": "s2"}},
    ]
    doctored = tmp_path / "flight.1b.json"
    doctored.write_text(json.dumps(payload))
    probs = obs_dump.validate_flight(str(doctored), attempt=1)
    assert any("ring order" in p for p in probs), probs
    # doctored spans that aren't even objects (or carry junk ts) must
    # REPORT, never raise — that is the fails-loudly contract
    junk = tmp_path / "flight.2.json"
    junk.write_text(json.dumps({
        "schema": "ds-flight-v1", "wall_time": 0, "ticks": 1,
        "spans": [None, 7, {"name": "a", "ph": "X", "ts": "x",
                            "dur": "y", "tid": "w#2",
                            "args": {"span_id": "s1"}}]}))
    probs = obs_dump.validate_flight(str(junk))
    assert sum("not an object" in p for p in probs) == 2, probs
    assert any("non-numeric ts" in p for p in probs), probs


def test_flight_validation_covers_worker_layout(tmp_path, params):
    """The exact artifact a SIGKILLed worker leaves behind validates:
    tid ``<name>#<attempt>`` spans in a ``flight.<attempt>.json`` ring
    written by the worker-side FlightRecorder."""
    obs_dump = _load_tool("obs_dump")
    from deepspeed_tpu.fleet.worker import flight_path
    from deepspeed_tpu.observability import FlightRecorder

    tracer = Tracer(tid="replica0#1")
    sched = _sched(params, tracer=tracer)
    fl = flight_path(str(tmp_path), 1)
    rec = FlightRecorder(tracer, fl, flush_every=1)
    rng = np.random.default_rng(4)
    sched.submit(rng.integers(0, CFG.vocab_size, size=(8,)).tolist(),
                 sampling=SamplingParams(greedy=True, max_new_tokens=4))
    while sched.num_pending:
        sched.step()
        rec.tick()
    assert fl.endswith("flight.1.json")
    assert obs_dump.validate_flight(fl) == [], obs_dump.validate_flight(fl)


# --------------------------------------------------------------------- #
# Tracer ring-wrap telemetry
# --------------------------------------------------------------------- #
def test_ring_wrap_counts_and_exports_truncation(params):
    """Satellite: a wrapped ring (a) counts overwritten records, (b)
    leads its export with a truncation note, (c) exposes
    observability/dropped_spans through the scheduler's registry."""
    tracer = Tracer(capacity=8)
    reg = MetricsRegistry()
    sched = _sched(params, tracer=tracer, registry=reg)
    rng = np.random.default_rng(5)
    for _ in range(3):
        sched.submit(rng.integers(0, CFG.vocab_size, size=(10,)).tolist(),
                     sampling=SamplingParams(greedy=True,
                                             max_new_tokens=8))
    sched.run_until_idle()
    assert tracer.dropped > 0
    events = tracer.export_events()
    note = events[0]
    assert note["name"] == "tracer/dropped_spans" and note["ph"] == "M"
    assert note["args"]["dropped_spans"] == tracer.dropped
    snap = reg.snapshot()
    assert snap["observability/dropped_spans"] == float(tracer.dropped)
    assert snap["observability/spans_recorded"] >= 8
    assert not reg.unknown_names, reg.unknown_names
    # the truncation note survives the Chrome export untouched
    obs_dump = _load_tool("obs_dump")
    assert obs_dump.validate_trace(events) == []
