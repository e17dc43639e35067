"""dslint tests: the tier-1 wiring (repo must lint clean against the
committed baseline), per-rule units against seeded good/bad snippets,
the Pallas contract checker against every seeded defect class (incl.
the PR-1 pltpu.ANY regression and a folded-layout d=64 BlockSpec), and
the runtime trace guard (recompile + host-sync detection, steady-state
train step, serving decode tick)."""

import importlib
import importlib.util
import pathlib
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, str(pathlib.Path(__file__).parent))

from deepspeed_tpu.analysis import registry
from deepspeed_tpu.analysis.common import Baseline, Finding
from deepspeed_tpu.analysis.jit_lint import lint_file
from deepspeed_tpu.analysis.pallas_lint import (capture_pallas_calls,
                                                check_captured_call,
                                                run_pallas_lint,
                                                _iter_pallas_sites)
from deepspeed_tpu.analysis.trace_guard import TraceGuard, TraceGuardError

REPO = pathlib.Path(__file__).resolve().parents[2]


def _tool(name):
    path = REPO / "tools" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ===================================================================== #
# Tier-1 wiring: the repo lints clean against the committed baseline.
# ONE full dslint run (both passes, JSON mode) is shared module-wide —
# the pallas capture alone costs ~7 s and must not be paid per test.
# ===================================================================== #
@pytest.fixture(scope="module")
def dslint_repo():
    import contextlib
    import io
    import json

    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        rc = _tool("dslint").run(["--format", "json"])
    return rc, json.loads(buf.getvalue())


def test_dslint_repo_clean(dslint_repo):
    """`python tools/dslint.py` must exit 0 on the repo: zero
    non-baselined findings across the jit lint AND the Pallas contract
    checker — and the committed baseline itself is EMPTY."""
    rc, report = dslint_repo
    assert rc == 0
    assert report["ok"] is True
    assert report["counts"] == {"new": 0, "baselined": 0}, report


def test_all_pallas_sites_registered_and_validated(dslint_repo):
    for mod in registry.KERNEL_MODULES:
        importlib.import_module(mod)
    sites = list(_iter_pallas_sites(str(REPO / "deepspeed_tpu")))
    # every file with a site is listed, and (at least) the historical 18
    # call sites; the one listed file without a site holds the cases of
    # the route over blocked_flash's kernels, beside that route (PR 42)
    listed = {m.replace(".", "/") + ".py" for m in registry.KERNEL_MODULES}
    assert listed - {s[0] for s in sites} == {
        "deepspeed_tpu/inference/v2/modules/attention.py"}
    assert {s[0] for s in sites} <= listed
    assert len(sites) >= 18
    _rc, report = dslint_repo
    assert not [f for f in report["new"] + report["baselined"]
                if f["rule"].startswith("pallas-")]


def test_unregistered_site_is_flagged(monkeypatch):
    # empty the registry (rather than popping one case) so the pass is
    # cheap — no case executes, and EVERY site must come back flagged
    monkeypatch.setattr(registry, "KERNEL_CASES", {})
    findings = run_pallas_lint()
    assert findings and all(f.rule == "pallas-unregistered-site"
                            for f in findings), \
        [f.format() for f in findings]
    assert any(f.path.endswith("ops/quantizer.py") for f in findings)


# ===================================================================== #
# Pallas contract checker: seeded defect classes
# ===================================================================== #
def _run_seeded(fn, **case_kw):
    case = registry.KernelCase(name="seeded", fn=fn, **case_kw)
    captured = []
    with capture_pallas_calls(captured):
        fn()
    assert captured, "seeded case reached no pallas_call"
    out = []
    for c in captured:
        out.extend(check_captured_call(case, c))
    return out


def _copy_kernel(x_ref, o_ref):
    o_ref[...] = x_ref[...]


def _rules(findings):
    return {f.rule for f in findings}


def test_checker_flags_mistiled_block():
    from jax.experimental import pallas as pl

    def bad():
        x = jnp.zeros((8, 512), jnp.float32)
        pl.pallas_call(
            _copy_kernel, grid=(1,),
            in_specs=[pl.BlockSpec((8, 100), lambda i: (0, 0))],
            out_specs=pl.BlockSpec((8, 512), lambda i: (0, 0)),
            out_shape=jax.ShapeDtypeStruct((8, 512), jnp.float32))(x)

    assert _rules(_run_seeded(bad)) == {"pallas-tiling"}


def test_checker_flags_folded_d64_lane_slice():
    """The folded-layout trap: a d=64 SINGLE-head lane block out of a
    [B, S, H*D] array is 64 lanes — half a lane tile. The shipped
    kernels group head PAIRS (hb=2 -> 128 lanes) precisely to avoid
    this; the checker must catch the naive spelling."""
    from jax.experimental import pallas as pl

    def bad():
        x = jnp.zeros((1, 512, 12 * 64), jnp.bfloat16)
        pl.pallas_call(
            _copy_kernel, grid=(12,),
            in_specs=[pl.BlockSpec((1, 512, 64), lambda h: (0, 0, h))],
            out_specs=pl.BlockSpec((1, 512, 64), lambda h: (0, 0, h)),
            out_shape=jax.ShapeDtypeStruct((1, 512, 768), jnp.bfloat16))(x)

    assert "pallas-tiling" in _rules(_run_seeded(bad))
    # ...and the shipped folded grouping (hb=2 -> 128-lane blocks) passes
    from deepspeed_tpu.ops import flash_attention as fa
    assert fa.folded_heads_per_block(12, 12, 64) == 2


def test_checker_flags_uncovered_tile():
    from jax.experimental import pallas as pl

    def bad():
        x = jnp.zeros((256, 128), jnp.float32)
        pl.pallas_call(
            _copy_kernel, grid=(2,),
            in_specs=[pl.BlockSpec((128, 128), lambda i: (i, 0))],
            out_specs=pl.BlockSpec((128, 128), lambda i: (0, 0)),
            out_shape=jax.ShapeDtypeStruct((256, 128), jnp.float32))(x)

    assert _rules(_run_seeded(bad)) == {"pallas-uncovered-tile"}
    # the waiver mechanism (gmm drhs empty-group contract) suppresses it
    assert _run_seeded(bad, allow=frozenset({"pallas-uncovered-tile"})) == []


def test_checker_flags_oob_index_map():
    from jax.experimental import pallas as pl

    def bad():
        x = jnp.zeros((256, 128), jnp.float32)
        pl.pallas_call(
            _copy_kernel, grid=(2,),
            in_specs=[pl.BlockSpec((128, 128), lambda i: (i + 1, 0))],
            out_specs=pl.BlockSpec((128, 128), lambda i: (i, 0)),
            out_shape=jax.ShapeDtypeStruct((256, 128), jnp.float32))(x)

    assert "pallas-index-map" in _rules(_run_seeded(bad))


def test_checker_reports_raising_index_map():
    """An index map that RAISES (e.g. walks off its block table) must
    become a finding with file:line context, not kill the lint run."""
    from jax.experimental import pallas as pl

    table = np.asarray([0])  # one entry, two grid points

    def bad():
        x = jnp.zeros((256, 128), jnp.float32)
        pl.pallas_call(
            _copy_kernel, grid=(2,),
            in_specs=[pl.BlockSpec((128, 128),
                                   lambda i: (int(table[i]), 0))],
            out_specs=pl.BlockSpec((128, 128), lambda i: (i, 0)),
            out_shape=jax.ShapeDtypeStruct((256, 128), jnp.float32))(x)

    findings = _run_seeded(bad)
    assert any(f.rule == "pallas-index-map" and "raised" in f.message
               for f in findings), [f.format() for f in findings]


def test_checker_flags_vmem_blowout():
    from jax.experimental import pallas as pl

    def bad():
        x = jnp.zeros((4096, 4096), jnp.float32)
        pl.pallas_call(
            _copy_kernel, grid=(1,),
            in_specs=[pl.BlockSpec((4096, 4096), lambda i: (0, 0))],
            out_specs=pl.BlockSpec((4096, 4096), lambda i: (0, 0)),
            out_shape=jax.ShapeDtypeStruct((4096, 4096), jnp.float32))(x)

    assert _rules(_run_seeded(bad)) == {"pallas-vmem-budget"}
    # a per-kernel override (kernels that manage residency) waives it
    assert _run_seeded(bad, vmem_limit=1 << 30) == []


def test_checker_accepts_good_call():
    from jax.experimental import pallas as pl

    def good():
        x = jnp.zeros((256, 256), jnp.bfloat16)
        pl.pallas_call(
            _copy_kernel, grid=(2, 2),
            in_specs=[pl.BlockSpec((128, 128), lambda i, j: (i, j))],
            out_specs=pl.BlockSpec((128, 128), lambda i, j: (i, j)),
            out_shape=jax.ShapeDtypeStruct((256, 256), jnp.bfloat16))(x)

    assert _run_seeded(good) == []


# ===================================================================== #
# jit lint: per-rule units on seeded snippets
# ===================================================================== #
def _lint_snippet(tmp_path, code):
    p = tmp_path / "snippet.py"
    p.write_text(textwrap.dedent(code))
    return lint_file(str(p))


def test_lint_wallclock_and_nprandom_in_jit(tmp_path):
    findings = _lint_snippet(tmp_path, """
        import time
        import numpy as np
        import jax

        @jax.jit
        def step_fn(x):
            t = time.time()
            noise = np.random.rand()
            return x * noise + t

        def host_fn(x):
            t = time.time()     # fine outside jit
            return x, t
    """)
    assert _rules(findings) == {"jit-wallclock", "jit-nprandom"}
    assert all(f.func == "step_fn" for f in findings)


def test_lint_kernel_body_and_jitref_contexts(tmp_path):
    findings = _lint_snippet(tmp_path, """
        import time
        import jax
        from jax.experimental import pallas as pl

        def _my_kernel(x_ref, o_ref):
            o_ref[...] = x_ref[...] * time.time()

        def run(x):
            return pl.pallas_call(_my_kernel, grid=(1,))(x)

        def _traced(x):
            global _STEPS
            return x

        jitted = jax.jit(_traced)
    """)
    assert _rules(findings) == {"jit-wallclock", "jit-global"}


def test_lint_tracer_is(tmp_path):
    findings = _lint_snippet(tmp_path, """
        import jax

        @jax.jit
        def pick(a, b):
            if a is b:
                return a
            if a is None:      # sentinel comparison stays legal
                return b
            return b
    """)
    assert [f.rule for f in findings] == ["jit-tracer-is"]


def test_lint_host_sync_in_step(tmp_path):
    findings = _lint_snippet(tmp_path, """
        import jax

        class Engine:
            def step(self, overflow):
                if bool(jax.device_get(overflow)):
                    self.skips += 1
                return overflow.item()

            def decode_step(self, flag, scale):
                got = jax.device_get(flag)          # bare form
                return got, float(jax.device_get(scale))

            def report(self, overflow):
                return bool(jax.device_get(overflow))  # cold path: ok
    """)
    # one finding per sync — the bool()-wrapped device_get must NOT be
    # double-reported for its inner call
    assert [f.rule for f in findings] == ["step-host-sync"] * 4
    assert [f.func for f in findings].count("step") == 2
    assert [f.func for f in findings].count("decode_step") == 2


def test_lint_timing_no_block(tmp_path):
    findings = _lint_snippet(tmp_path, """
        import time
        import jax

        def bench_bad(fn, x):
            t0 = time.time()
            y = fn(x)
            return time.time() - t0

        def bench_ok(fn, x):
            t0 = time.perf_counter()
            y = jax.block_until_ready(fn(x))
            return time.perf_counter() - t0

        def paced(arrivals):
            t0 = time.monotonic()          # pacing, not device timing
            return time.monotonic() - t0 < arrivals

        def bench_pc_no_block(fn, x):
            t0 = time.perf_counter()       # right clock, still no block
            y = fn(x)
            return time.perf_counter() - t0
    """)
    assert [f.rule for f in findings] == ["timing-no-block"] * 2
    assert [f.func for f in findings] == ["bench_bad", "bench_pc_no_block"]
    assert all("dispatch" in f.message for f in findings)


def test_lint_nested_function_reported_once(tmp_path):
    findings = _lint_snippet(tmp_path, """
        import time
        import jax

        def outer(fn, x):
            t0 = time.perf_counter()
            y = jax.block_until_ready(fn(x))   # outer blocks: clean
            dt = time.perf_counter() - t0

            def inner(z):
                t1 = time.time()
                w = fn(z)                      # inner never blocks
                return time.time() - t1

            return dt, inner
    """)
    # exactly ONE finding, attributed to the closure — and the inner
    # function's blocking-free bracket must not borrow outer's block
    assert [(f.rule, f.func) for f in findings] == \
        [("timing-no-block", "inner")]
    assert "dispatch" in findings[0].message


def test_lint_mutable_default_and_pltpu_any(tmp_path):
    findings = _lint_snippet(tmp_path, """
        from jax.experimental import pallas as pl
        from jax.experimental.pallas import tpu as pltpu

        def accumulate(x, acc=[]):
            acc.append(x)
            return acc

        SPEC = pl.BlockSpec(memory_space=pltpu.ANY)
    """)
    assert _rules(findings) == {"mutable-default", "pltpu-any"}


def test_lint_sync_in_transfer_loop(tmp_path):
    """Per-leaf blocking calls inside a transfer-shaped function's loop
    are flagged; the batched form (one device_put/device_get outside
    the loop) and the opt-in timed_wait profiling helper are not."""
    findings = _lint_snippet(tmp_path, """
        import jax

        def _offload_restore(leaves, shardings):
            out = []
            for leaf, sh in zip(leaves, shardings):
                arr = jax.device_get(leaf)          # serial round-trip
                moved = jax.device_put(arr, sh)
                moved.block_until_ready()           # waits per leaf too
                out.append(moved)
            return out

        def _spill_scalars(stats, flags):
            k = 0
            while k < len(flags):
                stats.record(flags[k].item())       # .item() per leaf
                k += 1
            return stats

        def _offload_restore_batched(leaves, shardings, stats):
            moved = jax.device_put(list(leaves), list(shardings))
            for m in moved:
                stats.note_restore(m.nbytes, overlapped=True)
                stats.timed_wait(m)   # named opt-in profile helper: ok
            return moved

        def reduce_losses(losses):
            total = 0.0
            for loss in losses:
                total += jax.device_get(loss)  # not a transfer fn: ok
            return total
    """)
    hits = sorted((f for f in findings
                   if f.rule == "sync-in-transfer-loop"),
                  key=lambda f: f.line)
    assert [(f.func, f.message.split(" inside")[0]) for f in hits] == [
        ("_offload_restore", "jax.device_get(...)"),
        ("_offload_restore", "moved.block_until_ready(...)"),
        ("_spill_scalars", ".item()"),
    ]
    assert all("batched" in f.hint and "timed_wait" in f.hint
               for f in hits)


def test_lint_transfer_loop_nested_helper_and_loop(tmp_path):
    """A helper DEFINED inside the loop is the helper's own finding
    (not the enclosing transfer function's), and a call in a nested
    loop is reported exactly once."""
    findings = _lint_snippet(tmp_path, """
        import jax

        def _transfer_buckets(buckets):
            for bucket in buckets:
                def fetch_one(leaf):               # helper defn in loop
                    return jax.device_get(leaf)
                for leaf in bucket:
                    got = jax.device_get(leaf)     # ONE finding
            return None
    """)
    hits = [(f.func, f.line) for f in findings
            if f.rule == "sync-in-transfer-loop"]
    # exactly one finding despite the doubly-nested loop; the nested
    # helper's device_get is not attributed to _transfer_buckets (its
    # name has no transfer marker, so it produces no finding at all)
    assert len(hits) == 1
    assert hits[0][0] == "_transfer_buckets"


def test_lint_repo_package_clean(dslint_repo):
    _rc, report = dslint_repo
    assert not [f for f in report["new"] + report["baselined"]
                if not f["rule"].startswith("pallas-")]


# ===================================================================== #
# Metric-name registry lint (pass 3)
# ===================================================================== #
def test_metrics_lint_repo_clean(dslint_repo):
    """Every metric-shaped string literal in the repo matches a declared
    registry name (checked by the shared full dslint run, which scans
    deepspeed_tpu/ + tools/ + the benches)."""
    _rc, report = dslint_repo
    assert not [f for f in report["new"] + report["baselined"]
                if f["rule"] == "metric-name"]


def test_metrics_lint_catches_typos(tmp_path):
    from deepspeed_tpu.analysis.metrics_lint import run_metrics_lint

    src = textwrap.dedent("""
        def export(m, k):
            m.write("serving/prefx_hits", 1)      # typo'd exact name
            m.write("fleet/quarantined", 2)       # declared: clean
            m.write(f"serving/spec_{k}", 3)       # declared family: clean
            m.write(f"fleet/specc_{k}", 4)        # typo'd family prefix
            m.write(f"resilience/{k}", 5)         # bare ns: indeterminate
            s = "serving/* scalars and prose"     # docstring-ish: skipped
    """)
    p = tmp_path / "m.py"
    p.write_text(src)
    findings = run_metrics_lint([str(p)])
    assert len(findings) == 2, findings
    assert all(f.rule == "metric-name" for f in findings)
    msgs = " | ".join(f.message for f in findings)
    assert "serving/prefx_hits" in msgs and "fleet/specc_" in msgs


def test_metrics_lint_declarations_loaded():
    """The declaring modules' import populates the default registry with
    every namespace the stack emits."""
    from deepspeed_tpu.analysis.metrics_lint import declared_specs

    names = {s.name for s in declared_specs()}
    assert "serving/finished" in names
    assert "fleet/quarantined" in names
    assert "resilience/saves" in names
    assert "fleet/router_*" in names


# ===================================================================== #
# Baseline mechanics
# ===================================================================== #
def test_baseline_fingerprint_ignores_line_moves(tmp_path):
    f1 = Finding(rule="r", path="a.py", line=10, func="f", message="m")
    f2 = Finding(rule="r", path="a.py", line=99, func="f", message="m")
    f3 = Finding(rule="r", path="a.py", line=10, func="g", message="m")
    assert f1.fingerprint == f2.fingerprint != f3.fingerprint

    bl = Baseline.from_findings([f1])
    new, old = bl.split([f2, f3])
    assert new == [f3] and old == [f2]

    path = tmp_path / "baseline.json"
    bl.save(str(path))
    assert Baseline.load(str(path)).is_suppressed(f2)
    assert not Baseline.load(str(tmp_path / "missing.json")).is_suppressed(f1)


# ===================================================================== #
# Trace guard: recompiles, host syncs, steady-state regions
# ===================================================================== #
def test_trace_guard_detects_recompile(trace_guard):
    f = jax.jit(lambda a: a * 2 + 1)
    f(jnp.ones((4, 4)))  # warm
    with trace_guard(max_compiles=0, label="warm call"):
        f(jnp.ones((4, 4)))  # cached: fine
    with pytest.raises(TraceGuardError, match="recompiled"):
        with trace_guard(max_compiles=0, label="cold call"):
            f(jnp.ones((5, 5)))  # new shape


def test_trace_guard_counts_host_syncs(trace_guard):
    x = jnp.ones((4,))
    orig_device_get = jax.device_get
    orig_block = jax.block_until_ready
    with trace_guard(max_compiles=None) as tg:
        jax.device_get(x)
        jax.block_until_ready(x)
    assert tg.host_syncs == 2
    # the guard must restore the real functions on exit
    assert jax.device_get is orig_device_get
    assert jax.block_until_ready is orig_block
    with pytest.raises(TraceGuardError, match="host sync"):
        with trace_guard(max_compiles=None, max_host_syncs=0):
            jax.device_get(x)


def test_trace_guard_steady_state_train_step(trace_guard):
    """MiniEngine stand-in for the full-engine test (test_engine.py's
    variant needs the mesh APIs this host may lack): a jitted
    loss+grad+update step must be compile- and sync-free once warm."""
    @jax.jit
    def train_step(params, x, y):
        def loss_fn(p):
            pred = x @ p["w"] + p["b"]
            return jnp.mean((pred - y) ** 2)

        loss, g = jax.value_and_grad(loss_fn)(params)
        return ({k: params[k] - 0.1 * g[k] for k in params}, loss)

    params = {"w": jnp.zeros((8, 8), jnp.float32),
              "b": jnp.zeros((8,), jnp.float32)}
    x = jnp.asarray(np.random.default_rng(0).normal(size=(4, 8)),
                    jnp.float32)
    y = x @ jnp.ones((8, 8), jnp.float32)
    for _ in range(2):
        params, loss = train_step(params, x, y)
    with trace_guard(max_compiles=0, max_host_syncs=0,
                     label="mini train step") as tg:
        for _ in range(3):
            params, loss = train_step(params, x, y)
    assert tg.compiles == 0 and tg.host_syncs == 0
    assert float(jax.device_get(loss)) >= 0.0  # still a real loss


def test_serving_decode_tick_recompile_free():
    """The warmed-up ContinuousBatchScheduler decode tick builds zero
    new executables (tools/serving_smoke.run_decode_guard raises
    TraceGuardError otherwise)."""
    out = _tool("serving_smoke").run_decode_guard(n_ticks=3, warm_ticks=2)
    assert out["compiles"] == 0
    # the only sanctioned host syncs are the explicit per-tick logits
    # fetches
    assert out["host_syncs"] <= out["guarded_ticks"]
