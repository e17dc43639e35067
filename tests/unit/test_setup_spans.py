"""PR 64: set-up measured inside the program.

* one ``setup/build_program`` record an executable on ``process_tracer()``,
  folded from ``jax.monitoring``'s events: its phases, what the persistent
  cache said, the program's own name;
* ``setup/import``, ``setup/engine_init`` and ``setup/init_parameters``, and
  the builds of an engine's first step under the name its dispatch span
  carries;
* a build inside a tick is ONE record, on the process tracer, and closes
  inside the dispatch span that caused it (the benchmark's reader names that
  span by time); builds on several threads lose no record and hang under
  their own thread's span;
* ``TraceGuard`` and ``chip_smoke.CompileClock`` count through the same
  listener;
* the operator's counters;
* the benchmark's reader over hand-made records.
"""

import os
import pathlib
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.analysis.trace_guard import TraceGuard, compile_count
from deepspeed_tpu.inference.v2 import (InferenceEngineV2,
                                        RaggedInferenceEngineConfig)
from deepspeed_tpu.inference.v2.model_implementations import RaggedLlama
from deepspeed_tpu.models import LlamaConfig, LlamaForCausalLM
from deepspeed_tpu.observability import (MetricsRegistry, Tracer,
                                         process_tracer, tracer as tracer_mod)
from deepspeed_tpu.serving import ContinuousBatchScheduler, SamplingParams

ROOT = pathlib.Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(pathlib.Path(__file__).parent))

CFG = LlamaConfig.tiny(dtype=jnp.float32)
BUILD = "setup/build_program"


@pytest.fixture(scope="module")
def params():
    return LlamaForCausalLM(CFG).init(
        jax.random.key(0), np.zeros((1, 4), np.int32))["params"]


def _engine(params, budget=32):
    cfg = RaggedInferenceEngineConfig.from_dict({
        "state_manager": {"max_ragged_batch_size": budget,
                          "max_ragged_sequence_count": 4,
                          "max_context": 48},
        "kv_cache": {"block_size": 8, "num_blocks": 17}})
    return InferenceEngineV2(RaggedLlama(CFG, 8), params, cfg)


def _prompt(n, seed=0):
    return np.random.default_rng(seed).integers(
        0, CFG.vocab_size, size=(n,)).tolist()


def _since(n):
    """The process tracer's records after its first ``n`` ever written."""
    tr = process_tracer()
    return tr.records()[-(tr._n - n):] if tr._n > n else []


def _builds(recs, program=None):
    return [r for r in recs if r["name"] == BUILD
            and program in (None, r["attrs"]["program"])]


# --------------------------------------------------------------------- #
# (a) one record a build: phases, cache outcome, name
# --------------------------------------------------------------------- #
@pytest.fixture
def cache_dir(tmp_path):
    """A persistent compile cache of the test's own that keeps every
    executable, however small and quick; the process's setting after."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    # (an engine built earlier in the process has put source locations into
    # the key, ``key_cache_on_names``: the second build below is called from
    # another line of the test, which would be another program)
    keys = {"jax_compilation_cache_dir": str(tmp_path / "cache"),
            "jax_persistent_cache_min_compile_time_secs": 0,
            "jax_persistent_cache_min_entry_size_bytes": -1,
            "jax_compilation_cache_include_metadata_in_key": False}
    before = {k: getattr(jax.config, k) for k in keys}
    cc.reset_cache()
    for k, v in keys.items():
        jax.config.update(k, v)
    yield keys["jax_compilation_cache_dir"]
    for k, v in before.items():
        jax.config.update(k, v)
    cc.reset_cache()


def _fresh_program(name):
    """A jitted function nobody has built, calling a jitted function."""
    salt = float(time.monotonic_ns() % 9973)

    @jax.jit
    def inner_of_the_test(x):
        return jnp.sin(x) @ x

    def program(x):
        return inner_of_the_test(x) + salt

    program.__name__ = program.__qualname__ = name
    return jax.jit(program)


@pytest.mark.parametrize("case", ["miss_then_hit", "no_directory"])
def test_one_record_a_build(case, request):
    if case == "miss_then_hit":
        request.getfixturevalue("cache_dir")
    else:
        before = jax.config.jax_compilation_cache_dir
        jax.config.update("jax_compilation_cache_dir", None)
        request.addfinalizer(lambda: jax.config.update(
            "jax_compilation_cache_dir", before))
    name = f"program_of_{case}"
    f, x = _fresh_program(name), jnp.ones((8, 8))
    n0 = process_tracer()._n
    t0 = time.monotonic_ns()
    f(x)
    f(x)                                # in memory: no build, no record
    first = _builds(_since(n0), name)
    assert len(first) == 1
    rec, a = first[0], first[0]["attrs"]
    assert a["trace_s"] > 0 and a["lower_s"] > 0 and a["backend_s"] > 0
    # the inner function's trace is inside the outer's, not a program
    assert not _builds(_since(n0), "inner_of_the_test")
    # end = the backend event's instant, start = the trace's start
    assert t0 <= rec["t0_ns"] < rec["t1_ns"] <= time.monotonic_ns()
    assert (rec["t1_ns"] - rec["t0_ns"]) / 1e9 >= \
        0.99 * (a["trace_s"] + a["lower_s"] + a["backend_s"])
    assert rec["ph"] == "X" and rec["trace_id"] and rec["span_id"]
    if case == "no_directory":
        assert a["cache"] == "none"
        assert "retrieval_s" not in a
        return
    assert a["cache"] == "miss"
    jax.clear_caches()
    n1 = process_tracer()._n
    f(x)
    again = _builds(_since(n1), name)
    assert len(again) == 1
    b = again[0]["attrs"]
    assert b["cache"] == "hit" and b["retrieval_s"] > 0
    assert b["program"] == a["program"] == name
    assert b["seq"] > a["seq"]


def test_a_compile_ahead_of_time_is_a_record_of_its_backend_alone():
    f = _fresh_program("program_compiled_ahead")
    lowered = f.lower(jnp.ones((4, 4)))
    jnp.ones((3, 3)) + 2                # another build in between
    n0 = process_tracer()._n
    lowered.compile()
    (rec,) = _builds(_since(n0), "program_compiled_ahead")
    assert "lower_s" not in rec["attrs"] and rec["attrs"]["backend_s"] > 0


# --------------------------------------------------------------------- #
# (b) the setup/* spans of a process and of both engines
# --------------------------------------------------------------------- #
def test_setup_import_closes_with_the_process_start():
    # kept beside the ring: a test process builds more than the ring holds
    imp = process_tracer().import_span
    assert imp["name"] == "setup/import" and imp["ph"] == "X"
    assert imp["t0_ns"] == deepspeed_tpu._IMPORT_OPEN_NS < imp["t1_ns"]
    began = imp["attrs"]["process_start_ns"]
    assert began <= imp["t0_ns"]
    if sys.platform.startswith("linux"):
        # the kernel's account: before the import, within this process
        assert 0 < imp["t0_ns"] - began < 3600e9
    # once a process
    tracer_mod.process_began(time.monotonic_ns())
    assert process_tracer().import_span is imp


def test_process_start_falls_back_to_the_spans_own_open(monkeypatch):
    monkeypatch.setattr(tracer_mod.time, "clock_gettime",
                        lambda *_: (_ for _ in ()).throw(OSError()))
    assert tracer_mod._process_start_ns(123) == 123


def test_serving_engine_leaves_engine_init_and_names_its_builds(params):
    n0 = process_tracer()._n
    eng = _engine(params)
    (init,) = [r for r in _since(n0) if r["name"] == "setup/engine_init"]
    # its length is what is read of it (what the engine holds on the device
    # is ``occupancy()``'s to say)
    assert "attrs" not in init and init["t0_ns"] < init["t1_ns"]
    # builds the constructor made hang under its span
    inside = [r for r in _builds(_since(n0))
              if init["t0_ns"] <= r["t0_ns"] and r["t1_ns"] <= init["t1_ns"]]
    assert all(r["parent"] == init["span_id"] for r in inside)
    tr = Tracer()
    sched = ContinuousBatchScheduler(eng, tracer=tr)
    n1 = process_tracer()._n
    sched.submit(_prompt(13), SamplingParams(greedy=True, max_new_tokens=4))
    sched.run_until_idle()
    dispatched = {r["attrs"]["program"] for r in tr.records()
                  if r["name"] in ("engine/ragged_step",
                                   "engine/decode_step")}
    assert dispatched == {"ragged_step_T16", "decode_step"}
    built = {r["attrs"]["program"] for r in _builds(_since(n1))}
    assert dispatched <= built          # letter for letter


def test_training_engine_leaves_engine_init_and_init_parameters():
    from simple_model import SimpleModel, random_batch

    model = SimpleModel(hidden_dim=8)
    n0 = process_tracer()._n
    engine, *_ = deepspeed_tpu.initialize(
        model=(model.init, model.apply),
        config={"train_micro_batch_size_per_gpu": 2,
                "optimizer": {"type": "Adam", "params": {"lr": 1e-2}}})
    x, y = random_batch(2 * engine.dp_world_size, 8)
    engine.initialize_parameters(x, y)
    recs = _since(n0)
    (init,) = [r for r in recs if r["name"] == "setup/engine_init"]
    (par,) = [r for r in recs if r["name"] == "setup/init_parameters"]
    assert init["t1_ns"] <= par["t0_ns"] < par["t1_ns"]
    assert "attrs" not in init and "attrs" not in par
    # the sharded init is a build of its own, under the span
    (build,) = _builds(recs, "build")
    assert build["parent"] == par["span_id"]
    assert build["trace_id"] == par["trace_id"] == init["trace_id"]
    # the engine's provider exports the operator's view
    reg = MetricsRegistry()
    engine.register_observability(reg)
    loss = engine(x, y)
    engine.backward(loss)
    engine.step()
    snap = reg.snapshot()
    assert snap["observability/programs_built"] >= 1
    assert snap["observability/program_build_seconds"] > 0
    assert snap["observability/time_to_first_launch_s"] > 0
    assert not reg.unknown_names


def test_a_constructor_that_raises_closes_its_span(params):
    n0 = process_tracer()._n
    cfg = RaggedInferenceEngineConfig.from_dict({
        "state_manager": {"max_context": 1 << 20}})

    class Short(RaggedLlama):
        max_positions = 16

    with pytest.raises(ValueError, match="position table"):
        InferenceEngineV2(Short(CFG, 8), params, cfg)
    (init,) = [r for r in _since(n0) if r["name"] == "setup/engine_init"]
    assert "attrs" not in init
    assert process_tracer()._current is None
    assert not process_tracer().open_spans()


# --------------------------------------------------------------------- #
# (c) a build inside a tick closes inside the dispatch that caused it
# --------------------------------------------------------------------- #
def test_a_build_inside_a_tick_is_found_under_its_dispatch_by_time(params):
    from benchmark.readers import setup_build_s

    tr = Tracer()
    sched = ContinuousBatchScheduler(_engine(params), tracer=tr)
    greedy = lambda n: SamplingParams(greedy=True, max_new_tokens=n)
    sched.submit(_prompt(13), greedy(3))
    sched.run_until_idle()              # ragged_step_T16, decode_step
    n0, p0 = len(tr.records()), process_tracer()._n
    sched.submit(_prompt(29, 1), greedy(2))     # a new bucket: T32
    sched.run_until_idle()
    # ONE record, on the process tracer; none on the scheduler's
    assert not _builds(tr.records())
    (build,) = _builds(_since(p0), "ragged_step_T32")
    assert build["parent"] is None      # no setup/* span is open
    # both tracers read one clock: the dispatch whose interval holds it
    (dispatch,) = [r for r in tr.records()[n0:]
                   if r["name"] == "engine/ragged_step"
                   and r["t0_ns"] <= build["t1_ns"] <= r["t1_ns"]]
    assert dispatch["attrs"]["program"] == "ragged_step_T32"
    assert dispatch["t0_ns"] <= build["t0_ns"]
    spans = setup_build_s._tick_spans({"tracer_records": tr.records()})
    by_id = {r["span_id"]: r for r in spans}
    tick = by_id[by_id[dispatch["parent"]]["parent"]]
    assert setup_build_s._caused_by(spans, build["t1_ns"]) == \
        f"engine/ragged_step <- prefill <- tick {tick['attrs']['tick']}"
    # the first dispatch record that names a program is the one that built
    # it; a later launch of it builds nothing
    p1 = process_tracer()._n
    sched.submit(_prompt(27, 2), greedy(2))
    sched.run_until_idle()
    assert not _builds(_since(p1))
    launches = [r for r in tr.records() if r["name"] == "engine/ragged_step"
                and r["attrs"]["program"] == "ragged_step_T32"]
    assert len(launches) >= 2 and launches[0] is not launches[-1]
    assert launches[0]["span_id"] == dispatch["span_id"]
    assert all(set(r["attrs"]) == {"launch", "program"} for r in launches)


def test_builds_on_several_threads_lose_nothing_and_keep_their_parents():
    import threading

    n_threads, n_each = 4, 6
    start = threading.Barrier(n_threads)

    @tracer_mod.setup_span("setup/of_a_thread")
    def work(k):
        start.wait()
        for j in range(n_each):
            _fresh_program(f"program_of_thread_{k}_{j}")(jnp.ones((2, 2)))

    n0 = process_tracer()._n
    threads = [threading.Thread(target=work, args=(k,))
               for k in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    recs = _since(n0)
    spans = [r for r in recs if r["name"] == "setup/of_a_thread"]
    assert len(spans) == n_threads
    assert all(r["parent"] is None for r in spans)  # not each other's
    mine = {}
    for r in _builds(recs):
        if r["attrs"]["program"].startswith("program_of_thread_"):
            mine.setdefault(r["parent"], set()).add(r["attrs"]["program"])
    assert set(mine) == {r["span_id"] for r in spans}
    for programs in mine.values():      # one thread's, all of them
        assert len(programs) == n_each
        assert len({p.split("_")[3] for p in programs}) == 1
    assert len({r["attrs"]["seq"] for r in _builds(recs)}) == \
        len(_builds(recs))
    assert process_tracer()._current is None
    assert not process_tracer().open_spans()


# --------------------------------------------------------------------- #
# (e) TraceGuard counts through the shared listener
# --------------------------------------------------------------------- #
def test_trace_guard_counts_through_the_one_listener():
    from jax._src import monitoring as _monitoring

    ours = [f for f in _monitoring.get_event_duration_listeners()
            if getattr(f, "__module__", "").startswith(
                ("deepspeed_tpu", "chip_smoke"))]
    assert ours == [tracer_mod._on_build_seconds]
    f = _fresh_program("program_under_guard")
    c0, n0 = compile_count(), process_tracer()._n
    with TraceGuard(max_compiles=None) as tg:
        f(jnp.ones((6, 6)))
    assert tg.compiles == compile_count() - c0 == len(_builds(_since(n0)))
    assert tg.compiles >= 1 and tg.retraces >= tg.compiles
    with TraceGuard(max_compiles=0) as tg:
        f(jnp.ones((6, 6)))
    assert tg.compiles == 0 and tg.retraces == 0


def test_chip_smoke_clock_reads_the_one_listener():
    import chip_smoke

    clock = chip_smoke.CompileClock()
    n0 = process_tracer()._n
    _fresh_program("program_under_the_clock")(jnp.ones((5, 5)))
    took = clock.take()
    built = _builds(_since(n0))
    assert took["programs_built"] == len(built) >= 1
    assert took["compile_s"] == pytest.approx(
        sum(r["attrs"]["backend_s"] for r in built), abs=0.006)
    assert clock.take() == {"compile_s": 0.0, "programs_built": 0}


def test_serving_provider_exports_the_operators_view(params):
    eng = _engine(params)
    reg = MetricsRegistry()
    sched = ContinuousBatchScheduler(eng, registry=reg)
    cold = reg.snapshot()
    assert "observability/time_to_first_launch_s" not in cold
    sched.submit(_prompt(13), SamplingParams(greedy=True, max_new_tokens=3))
    sched.run_until_idle()
    snap = reg.snapshot()
    assert snap["observability/programs_built"] >= \
        cold["observability/programs_built"]
    assert snap["observability/program_build_seconds"] > 0
    assert snap["observability/program_cache_misses"] >= 0
    assert snap["observability/time_to_first_launch_s"] > 0
    # a scrape between warm ticks builds nothing and syncs nothing
    with TraceGuard(max_compiles=0, max_host_syncs=0):
        again = reg.snapshot()
    assert again["observability/time_to_first_launch_s"] == \
        snap["observability/time_to_first_launch_s"]
    assert not reg.unknown_names


# --------------------------------------------------------------------- #
# (f) the benchmark's reader over hand-made records
# --------------------------------------------------------------------- #
class _Ctx:
    def __init__(self, traffic=None):
        self.traffic = traffic or {}
        self.lines = []

    def log(self, msg):
        self.lines.append(msg)


def _span(name, t0_s, t1_s, **attrs):
    return {"name": name, "ph": "X", "tid": "process", "trace_id": "t",
            "span_id": f"{name}{t0_s}", "parent": None,
            "t0_ns": int(t0_s * 1e9), "t1_ns": int(t1_s * 1e9),
            **({"attrs": attrs} if attrs else {})}


def _build(program, t1_s, cache, seq, trace_s=0.5, lower_s=0.25,
           backend_s=1.0):
    return _span(BUILD, t1_s - trace_s - lower_s - backend_s, t1_s,
                 program=program, trace_s=trace_s, lower_s=lower_s,
                 backend_s=backend_s, cache=cache, seq=seq)


RECORDS = [
    _span("setup/import", 10.0, 12.0, process_start_ns=int(4e9)),
    _build("build", 15.0, "hit", 1),
    _span("setup/engine_init", 16.0, 17.0),
    _span("setup/init_parameters", 17.0, 20.0),
    _build("ragged_step_T64", 30.0, "hit", 2, backend_s=2.0),
    _build("convert_element_type", 31.0, "miss", 3, backend_s=0.125),
    _build("decode_step", 40.0, "none", 4, backend_s=4.0),
    # closes inside the window: not set-up
    _build("ragged_step_T256", 51.0, "miss", 5, backend_s=8.0),
    # after it: the harness's memory analysis
    _build("decode_step", 120.0, "hit", 6, backend_s=16.0),
]
READS = {
    "setup_engine_init_s": 4.0,
    "setup_trace_lower_s": 4 * 0.75,
    "setup_cache_read_s": 3.0,
    "setup_compile_s": 4.125,
    "setup_programs_compiled": 2,
}


@pytest.mark.parametrize("metric", sorted(READS))
@pytest.mark.parametrize("kind", ["serve", "train"])
def test_reader_cuts_at_the_window_and_filters(metric, kind):
    from benchmark.lib import spec
    from benchmark.readers import setup_build_s

    how = spec.layer_metric_file(metric)
    assert how["reader"] == "setup_build_s"
    facts = {"kind": kind, "process_records": list(RECORDS),
             "window_s": 51.0}
    if kind == "serve":
        facts.update(t_start_ns=int(50e9), t_stop_ns=int(101e9))
    else:       # the capture opens right after the window
        facts["capture"] = {"mono_sync_ns": int(101e9)}
    ctx = _Ctx({"preroll_s": 5.0})
    got = setup_build_s.read(facts, how["args"], ctx)
    assert got == pytest.approx(READS[metric])
    said = "\n".join(ctx.lines)
    assert "46.00 s by the program's own marks" in said
    assert "1 executable(s) built INSIDE the window: ragged_step_T256" in said
    assert "1 after it" in said
    # the three setup/* spans (2 + 1 + 3 s), not the gaps between them, and
    # the four builds before the window (1.75 + 2.75 + 0.875 + 4.75 s)
    assert "the setup/* spans hold 6.00 s, spans and build records " \
        "together cover 16.12 s = 35.1% of it" in said
    assert "    2.00 s  setup/import" in said
    assert "    4.00 s  setup/import -> setup/engine_init: " + (
        "the weights" if kind == "serve" else "the mesh") in said
    assert "ragged_step_T64" not in said.split(
        "did not hold: ")[1].split("\n")[0]
    # logged once a run
    n = len(ctx.lines)
    setup_build_s.read(facts, how["args"], ctx)
    assert len(ctx.lines) == n


def test_reader_names_the_span_that_caused_a_build_in_the_window():
    from benchmark.readers import setup_build_s

    tick = _span("tick", 50.5, 51.5, tick=7, kind="mixed")
    pre = dict(_span("prefill", 50.6, 51.4), parent=tick["span_id"])
    disp = dict(_span("engine/ragged_step", 50.7, 51.3, launch=9,
                      program="ragged_step_T256"),
                parent=pre["span_id"])
    # a request's phase is open then too, and is not what caused it
    phase = _span("request/decode", 45.0, 60.0)
    ladder = [_span("tick", 41.0, 42.0, tick=1), _span("tick", 43.0, 44.0,
                                                       tick=2)]
    first = dict(_span("engine/ragged_step", 41.1, 41.9, launch=1,
                       program="ragged_step_T64"),
                 parent=ladder[0]["span_id"])
    again = dict(_span("engine/ragged_step", 43.1, 43.9, launch=2,
                       program="ragged_step_T64"),
                 parent=ladder[1]["span_id"])
    facts = {"kind": "serve", "process_records": list(RECORDS),
             "t_start_ns": int(50e9), "t_stop_ns": int(101e9),
             "tracer_records": [tick, pre, disp, phase, again, first]
             + ladder}
    ctx = _Ctx({"preroll_s": 5.0})
    assert setup_build_s.read(
        facts, {"span": BUILD, "count": True}, ctx) == 4
    said = "\n".join(ctx.lines)
    assert "under engine/ragged_step <- prefill <- tick 7" in said
    assert "1 ticks with a program's first launch 1.00 s, 1 others " \
        "1.00 s, between ticks 1.00 s" in said
    assert "the check against the reference" in said
    assert "the pre-roll (5.0 s fixed)" in said


@pytest.mark.parametrize("facts", [
    {"kind": "serve", "t_start_ns": 5, "process_records": []},
    {"kind": "train", "process_records": list(RECORDS)},    # no window
], ids=["no_records", "no_window"])
def test_reader_returns_none_where_there_is_nothing_to_read(facts):
    from benchmark.readers import setup_build_s

    assert setup_build_s.read(facts, {"span": "setup/import"},
                              _Ctx()) is None


def test_reader_says_when_the_ring_has_gone_round():
    from benchmark.readers import setup_build_s

    facts = {"kind": "serve", "process_records": list(RECORDS),
             "process_dropped": 3, "t_start_ns": int(50e9)}
    ctx = _Ctx()
    setup_build_s.read(facts, {"span": BUILD, "count": True}, ctx)
    assert "3 records fell out of the process tracer's ring" in ctx.lines[0]


def test_reader_reads_the_live_process_tracer():
    """No ``process_records`` handed over: the run's own process tracer,
    ``setup/import`` among its records wherever the ring stands."""
    from benchmark.readers import setup_build_s

    facts = {"kind": "serve", "t_start_ns": time.monotonic_ns()}
    got = setup_build_s.read(facts, {"span": "setup/import"}, _Ctx())
    imp = process_tracer().import_span
    assert got == pytest.approx((imp["t1_ns"] - imp["t0_ns"]) / 1e9)
    assert facts["process_records"].count(imp) == 1
    assert facts["process_dropped"] == process_tracer().dropped


def test_proposed_entries_are_the_five_and_name_their_files():
    from benchmark.lib import spec

    proposed = spec.load_json(os.path.join(
        spec.BENCH_DIR, "tools", "calls", "pr64_results",
        "per_layer_proposed.json"))
    assert [m["name"] for m in proposed] == [
        "setup_engine_init_s", "setup_trace_lower_s",
        "setup_cache_read_s", "setup_compile_s", "setup_programs_compiled"]
    listed = {m["name"] for m in spec.benchmark_spec()["per_layer"]}
    for m in proposed:
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves"}
        assert m["moves"] == "setup_s" and m["better"] == "lower"
        assert m["layer"] == "set-up"
        assert m["source"] == ("program_counter" if m["unit"] == "count"
                               else "host_clock")
        assert spec.layer_metric_file(m["name"])["reader"] == "setup_build_s"
        assert m["name"] not in listed      # they wait as data
