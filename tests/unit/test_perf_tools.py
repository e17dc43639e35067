"""Performance-observability analysis layer: roofline/MFU waterfall
(attribution must sum to the measured step), the HLO memory ledger
(compile-time evidence + explicit unavailability), live occupancy
gauges (TraceGuard-clean), the perf_report renderer over real BENCH
history, the noise-aware perf_gate (pure compare logic + the tier-1
125M CPU smoke: unchanged re-run passes, seeded regression trips), and
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
                                         OpCost, Tracer, build_waterfall,
                                         chip_specs, kv_occupancy,
                                         mint_trace_id, phase_durations,
                                         tenant_occupancy,
                                         virtual_mesh_probe)
from deepspeed_tpu.observability.memory import tree_bytes
from deepspeed_tpu.observability.roofline import (attainable_seconds,
                                                  decode_tick_costs,
                                                  format_waterfall,
                                                  roofline_bound,
                                                  train_step_costs)
from deepspeed_tpu.serving import (ContinuousBatchScheduler, RequestState,
                                   SamplingParams)

CFG = LlamaConfig.tiny(dtype=jnp.float32)
_TOOLS = pathlib.Path(__file__).resolve().parents[2] / "tools"
_REPO = pathlib.Path(__file__).resolve().parents[2]


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
# Roofline model
# --------------------------------------------------------------------- #
def test_attainable_and_bound_verdicts():
    peak, bw = 100e12, 1e12
    # intensity 1000 > ridge 100 -> compute-bound
    assert roofline_bound(1e12, 1e9, peak, bw) == "compute"
    assert attainable_seconds(1e12, 1e9, peak, bw) == pytest.approx(0.01)
    # intensity 1 << ridge -> memory-bound
    assert roofline_bound(1e9, 1e9, peak, bw) == "memory"
    assert attainable_seconds(1e9, 1e9, peak, bw) == pytest.approx(1e-3)


def test_waterfall_attribution_sums_exactly():
    ops = [OpCost("a", flops=1e12, bytes=1e9, phase="decode"),
           OpCost("b", flops=1e9, bytes=4e9, phase="decode")]
    wf = build_waterfall(ops, measured_s=0.5, peak_flops=100e12,
                         hbm_bw=1e12, chip="test")
    assert wf.attributed_s == pytest.approx(0.5, rel=1e-12)
    assert {r.bound for r in wf.rows} == {"compute", "memory"}
    # the slower op (by attainable time) carries the larger share
    assert wf.rows[0].name == "a"
    assert 0 < wf.mfu < wf.mfu_attainable <= 1.0


def test_waterfall_phase_split_names_overhead():
    ops = [OpCost("gemm", flops=1e12, bytes=1e9, phase="decode")]
    phases = {"tick": 0.2, "decode": 0.12, "pack": 0.03}
    wf = build_waterfall(ops, measured_s=0.2, peak_flops=100e12,
                         hbm_bw=1e12, phase_seconds=phases)
    by_name = {r.name: r for r in wf.rows}
    assert by_name["gemm"].achieved_s == pytest.approx(0.12)
    assert by_name["host/pack"].bound == "overhead"
    assert by_name["host/unattributed"].achieved_s == pytest.approx(0.05)
    assert wf.attributed_s == pytest.approx(0.2, rel=1e-12)
    # rendering never raises and carries the verdict column
    assert "overhead" in format_waterfall(wf)
    # a modelled op whose phase the trace never measured is LOUD, not
    # silently dropped (the speculative-trace 'verify' vs 'decode' case)
    with pytest.raises(ValueError, match="verify"):
        build_waterfall(ops, measured_s=0.2, peak_flops=100e12,
                        hbm_bw=1e12,
                        phase_seconds={"tick": 0.2, "verify": 0.2})
    # a phase wrapping unmodelled DEVICE work is labeled as such, not
    # blamed on the host
    wf2 = build_waterfall(ops, measured_s=0.2, peak_flops=100e12,
                          hbm_bw=1e12,
                          phase_seconds={"tick": 0.2, "decode": 0.1,
                                         "prefill": 0.1})
    assert any(r.name == "unmodeled/prefill" for r in wf2.rows)


def test_waterfall_lane_scale_names_the_d64_culprit():
    """Same FLOPs/bytes, head_dim 64 vs 128: the d64 attention op's
    attainable time doubles (half the MXU lanes), dropping the
    geometry-attainable MFU — the honest-geometry gap, named per op."""
    d64 = train_step_costs(hidden=768, layers=12, heads=12,
                           intermediate=2048, vocab=32000, batch=8,
                           seq=1024, n_params=134_000_000)
    d128 = train_step_costs(hidden=768, layers=6, heads=6,
                            intermediate=2048, vocab=32000, batch=8,
                            seq=1024, n_params=134_000_000)
    att64 = next(o for o in d64 if "flash_attention" in o.name)
    att128 = next(o for o in d128 if "flash_attention" in o.name)
    assert att64.peak_scale == pytest.approx(0.5)
    assert att128.peak_scale == pytest.approx(1.0)
    wf64 = build_waterfall(d64, 0.084, 197e12, 819e9)
    wf128 = build_waterfall(d128, 0.084, 197e12, 819e9)
    assert wf64.mfu_attainable < wf128.mfu_attainable


def test_phase_durations_from_live_tracer_spans(params):
    tracer = Tracer(capacity=8192)
    sched = _sched(params, tracer=tracer)
    rng = np.random.default_rng(0)
    for _ in range(3):
        sched.submit(rng.integers(0, CFG.vocab_size, size=(12,)).tolist(),
                     sampling=SamplingParams(greedy=True,
                                             max_new_tokens=6))
    sched.run_until_idle()
    phases = phase_durations(tracer.export_events())
    assert phases["tick"] > 0
    assert "decode" in phases and "pack" in phases
    # a tick contains its phases
    assert phases["tick"] >= phases["decode"] * 0.5


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


def test_virtual_mesh_probe_tiny_zero3_on_this_host():
    """The reusable ROADMAP-item-3 evidence path: abstract ZeRO-3-style
    lowering on the host's (virtual) mesh — pure jit + NamedSharding —
    with REAL memory_analysis numbers (or an explicit unavailable record on
    backends that omit it)."""
    led = MemoryLedger()
    entry = virtual_mesh_probe("tiny_zero3", led)
    mem = entry["memory"]
    if not mem.get("available"):
        assert mem["reason"], mem      # explicit absence, never silent
        return
    assert mem["temp_size_in_bytes"] > 0
    assert entry["cost"]["flops"] > 0
    assert entry["meta"]["zero_stage"] == 3
    # unknown probe name -> explicit unavailable record too
    e2 = virtual_mesh_probe("nope", led)
    assert e2["memory"]["available"] is False


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
# perf_report
# --------------------------------------------------------------------- #
def test_perf_report_train_waterfall_from_bench_history():
    perf_report = _load_tool("perf_report")
    record = perf_report.load_bench_record(str(_REPO / "BENCH_r05.json"))
    text, summary = perf_report.build_report(record)
    # THE acceptance bar: attribution sums to 100% (+-2%) of the step
    assert abs(summary["attributed_pct"] - 100.0) <= 2.0
    assert "compute" in text and "memory" in text   # roofline verdicts
    assert "flash_attention(d64)" in text           # the named culprit
    wf = summary["waterfall"]
    assert wf["measured_s"] == pytest.approx(
        record["extra"]["step_time_ms"] / 1e3)


def test_perf_report_decode_waterfall_with_trace(params):
    """End-to-end: a traced tiny decode run -> record + trace ->
    waterfall whose rows (model ops + named host phases) sum to the
    measured tick."""
    perf_report = _load_tool("perf_report")
    tracer = Tracer(capacity=8192)
    sched = _sched(params, tracer=tracer)
    rng = np.random.default_rng(3)
    for _ in range(3):
        sched.submit(rng.integers(0, CFG.vocab_size, size=(12,)).tolist(),
                     sampling=SamplingParams(greedy=True,
                                             max_new_tokens=8))
    sched.run_until_idle()
    events = tracer.export_events()
    led = sched.engine.capture_memory_ledger()
    record = {
        "metric": "serving_scheduler_goodput_tokens_per_sec",
        "value": 1.0,
        "extra": {
            "max_concurrency": 3, "prompt_len": 12, "gen_tokens": 8,
            "platform": "cpu",
            "geometry": {"hidden": CFG.hidden_size,
                         "layers": CFG.num_hidden_layers,
                         "heads": CFG.num_attention_heads,
                         "kv_heads": CFG.num_key_value_heads,
                         "intermediate": CFG.intermediate_size,
                         "vocab": CFG.vocab_size, "dtype": "float32"},
            "memory_ledger": led.to_json(),
        },
    }
    text, summary = perf_report.build_report(record, events)
    assert abs(summary["attributed_pct"] - 100.0) <= 2.0
    assert "host/" in text                      # named host overhead
    assert "HLO memory ledger" in text
    assert "decode_step" in text
    # machine summary names the dominant row
    assert summary["top_op"]
    # ledger section renders explicit absences too
    led.record_unavailable("virtual_mesh/7b_zero3", "skipped: budget")
    record["extra"]["memory_ledger"] = led.to_json()
    text2, _ = perf_report.build_report(record, events)
    assert "UNAVAILABLE: skipped: budget" in text2


def test_perf_report_torn_trace_raises_not_zeroed():
    """A trace whose tick spans DID record child phases but none of
    them is the engine dispatch (ring wrapped past the decode spans)
    must raise — not attribute 0s to every model op; a tick-only trace
    (no child phases at all) falls back to whole-tick attribution."""
    perf_report = _load_tool("perf_report")
    record = {
        "metric": "serving_scheduler_goodput_tokens_per_sec",
        "value": 1.0,
        "extra": {"max_concurrency": 2, "prompt_len": 12,
                  "gen_tokens": 8, "platform": "cpu"},
    }
    tick = {"ph": "X", "name": "tick", "dur": 10_000.0,
            "args": {"span_id": "t0"}}
    pack_only = [tick, {"ph": "X", "name": "pack", "dur": 1_000.0,
                        "args": {"span_id": "p0", "parent": "t0"}}]
    with pytest.raises(ValueError, match="decode/verify"):
        perf_report.build_decode_waterfall(record, pack_only)
    # zero-MEDIAN engine phase is as torn as an absent one: decode
    # present in a minority of ticks medians to the 0.0 padding
    prefill_heavy = []
    for i, child in enumerate(["prefill", "prefill", "decode"]):
        prefill_heavy += [
            {"ph": "X", "name": "tick", "dur": 10_000.0,
             "args": {"span_id": f"t{i}"}},
            {"ph": "X", "name": child, "dur": 9_000.0,
             "args": {"span_id": f"c{i}", "parent": f"t{i}"}}]
    with pytest.raises(ValueError, match="decode/verify"):
        perf_report.build_decode_waterfall(record, prefill_heavy)
    # no child phases: whole-tick attribution, model ops carry the time
    wf = perf_report.build_decode_waterfall(record, [tick])
    assert wf.measured_s == pytest.approx(0.01)
    assert sum(r.achieved_s for r in wf.rows) == pytest.approx(0.01)
    assert max(r.flops for r in wf.rows) > 0


def test_waterfall_no_timings_keeps_mixed_phase_ops():
    """Without phase timings a mixed-phase op list shares the ONE
    measured window — no op silently drops out of the MFU accounting
    (the with-timings path raises on the same mismatch instead)."""
    ops = [OpCost("a", flops=1e12, bytes=1e9, phase="decode"),
           OpCost("b", flops=5e12, bytes=1e9, phase="verify")]
    wf = build_waterfall(ops, measured_s=0.5, peak_flops=197e12,
                         hbm_bw=819e9)
    assert {r.name for r in wf.rows} == {"a", "b"}
    assert wf.total_flops == pytest.approx(6e12)
    assert sum(r.achieved_s for r in wf.rows) == pytest.approx(0.5)


def test_decode_cost_model_tracks_engine_cost_analysis(params):
    """The analytic decode cost model vs the compiler: XLA's own flops
    count for the decode program must land within 2x of the model (the
    model counts matmuls; XLA adds elementwise/softmax tails)."""
    sched = _sched(params)
    led = sched.engine.capture_memory_ledger()
    entry = led.entries["decode_step"]
    if not entry["memory"].get("available"):
        pytest.skip("no cost analysis on this backend")
    S = 4                                       # max_seqs rows computed
    ops = decode_tick_costs(
        hidden=CFG.hidden_size, layers=CFG.num_hidden_layers,
        heads=CFG.num_attention_heads, kv_heads=CFG.num_key_value_heads,
        intermediate=CFG.intermediate_size, vocab=CFG.vocab_size,
        batch=S, context=17 * 8 / 4, dtype="float32")
    analytic = sum(o.flops for o in ops)
    compiled_flops = entry["cost"]["flops"]
    assert compiled_flops > 0
    assert 0.5 <= compiled_flops / analytic <= 2.0, \
        (compiled_flops, analytic)


# --------------------------------------------------------------------- #
# perf_gate
# --------------------------------------------------------------------- #
def _rec(value, noise=0.0, metric="perf_gate_decode_tick_ms"):
    return {"metric": metric, "value": value,
            "extra": {"noise_pct": noise}}


def test_gate_compare_logic_directions_and_noise():
    perf_gate = _load_tool("perf_gate")
    # lower-is-better: +5% inside the 10% tolerance, +15% out
    ok, _ = perf_gate.gate(_rec(105.0), [_rec(100.0)])
    assert ok
    ok, verdicts = perf_gate.gate(_rec(115.0), [_rec(100.0)])
    assert not ok and verdicts[0]["metric"] == "value"
    # a noisy measurement widens its own gate: 15% worse but 20% noise
    ok, _ = perf_gate.gate(_rec(115.0, noise=20.0), [_rec(100.0)])
    assert ok
    # higher-is-better records regress downward
    spec = [("value", "higher")]
    ok, _ = perf_gate.gate(_rec(88.0), [_rec(100.0)], specs=spec)
    assert not ok
    ok, _ = perf_gate.gate(_rec(95.0), [_rec(100.0)], specs=spec)
    assert ok
    # history median, not min/max: one outlier round cannot flip it
    ok, _ = perf_gate.gate(
        _rec(100.0), [_rec(99.0), _rec(101.0), _rec(50.0)], specs=spec)
    assert ok


def test_gate_never_passes_vacuously_or_on_broken_measurements():
    """Review fixes: (a) an all-skipped verdict list (schema drift —
    nothing was actually compared) FAILS the gate; (b) a non-positive
    fresh value on a lower-is-better metric is a broken measurement,
    not an infinite speedup."""
    perf_gate = _load_tool("perf_gate")
    # wrong-shaped record: 'value' lives somewhere else entirely
    wrapped = {"metric": "perf_gate_decode_tick_ms",
               "parsed": {"value": 200.0}}
    ok, verdicts = perf_gate.gate(wrapped, [wrapped])
    assert not ok
    assert any(v["status"] == "invalid" for v in verdicts), verdicts
    # broken measurement: 0 ms/tick must not gate as a pass
    ok, verdicts = perf_gate.gate(_rec(0.0), [_rec(100.0)])
    assert not ok
    assert verdicts[0]["status"] == "invalid"


def test_perf_gate_smoke_125m_cpu():
    """Acceptance: the gate passes on an unchanged re-run and fails
    (naming the metric) on a seeded >=10% regression — measured on the
    real 125M-geometry decode program, interleaved paired arms."""
    snap = _load_tool("perf_gate").run_smoke()
    assert snap["perf_gate_smoke"] == "ok"
    assert snap["regressed_metric"] == "value"
    assert snap["seeded_ratio"] > 1.10
    assert abs(snap["rerun_ratio"] - 1.0) <= 0.10


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
