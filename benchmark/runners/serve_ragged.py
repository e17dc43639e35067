"""Serving runner: ``RaggedLlama`` -> ``InferenceEngineV2`` ->
``ContinuousBatchScheduler.submit`` / ``step``, the calls a user makes
(as ``chip_smoke.py`` does).  One process holds the chip: the load
generator, the scheduler loop and the profiler all live in it.

Set-up: bf16 weights made on the device in ONE jitted call from ``--seed``;
the engine with the configuration's KV pool; prefill + decode logits of one
prompt against the plain float32 reference; a ladder of prompt lengths, each
served alone and beside a decoding sequence, which meets every token-count
shape the engine buckets to; then ``preroll_s`` seconds of the cell's own
traffic, so the window starts on a system in its steady state and any
program the traffic still builds is built (and counted) before it.

Every tick of the window (when, how long, tokens out, KV blocks held) and
every request go to ``bench_out/<cell>/window_seed<n>.json``; the three
longest ticks are printed, so a run that lost seconds to a stall says where.

Timing: the host clock (``time.monotonic``, the clock of the program's
Tracer too).  A request's first-token
time is counted from when it was DUE (open loop: its scheduled arrival;
closed loop: the moment its client's previous request finished), through
the ``on_token`` hook of ``submit`` — not from ``Request.ttft``, which
starts at ``submit`` and hides a stalled generator.  ``total_tok_s`` counts
the tokens processed inside the window whichever request they belong to:
generated tokens when they are emitted, prompt tokens spread evenly between
their request's submission and its first token
(``stats.prompt_tokens_between``).  Generated tokens alone swing by 5-9%
between runs of one code, because what is in flight at the window's edges
is a fifth of a closed loop's work; with the prompts the spread is 1-2%
(PERF.md, PR 22).  ``itl_p50_ms`` is the median over every gap between two
consecutive tokens of a request: what a pure-decode tick costs a user.  TTFT and
TPOT are over the requests due inside it; after the window nothing new is
submitted and those requests are drained for at most ``drain_s``.
"""

from __future__ import annotations

import json
import os
import time
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from benchmark.lib import device, spec, stats, tracing, traffic

# Engine logits (bf16 weights and activations, paged Pallas attention)
# against the float32 reference on the same weights, at the last prompt
# position and 8 decoded positions, as the largest absolute difference over
# the largest reference logit.  bf16 keeps 8 mantissa bits: ~10 roundings a
# layer, each 2^-9 relative on average, drift apart over 16 layers to about
# one percent of the logit scale.  Measured on the chip over 29 runs and
# seeds: 0.0056 .. 0.0173, median 0.0067 (PERF.md, PR 22).  A wrong block
# table, position, mask or rope moves logits by their full scale, and an
# int8 / fp8 weight or KV path by several percent.
LOGIT_TOL = 0.03


class _Track:
    """What the harness itself records about one request."""

    __slots__ = ("plan", "due", "submitted", "token_times", "req", "in_window")

    def __init__(self, plan, due: float, in_window: bool):
        self.plan = plan
        self.due = due
        self.submitted: Optional[float] = None
        self.token_times: List[float] = []
        self.req = None
        self.in_window = in_window

    @property
    def done(self) -> bool:
        """Terminal in the scheduler (finished, or failed for good)."""
        return self.req is not None and self.req.finish_reason is not None

    @property
    def ok(self) -> bool:
        return self.done and len(self.token_times) >= self.plan.output_len


def make_params(family, hf, seed: int):
    """Every leaf of the serving parameter tree in bf16, born on the device
    in one jitted call from the seed (no float32 copy, nothing on the host).
    XLA's own bit generator (``rbg``) rather than threefry: several times
    faster for billions of values, and as reproducible from a seed."""
    import jax
    import jax.numpy as jnp

    shapes = family.serve_param_shapes(hf)
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    stds = [family.init_std([str(getattr(p, "key", p)) for p in path],
                            leaf.shape) for path, leaf in flat]

    def build(key):
        leaves = []
        for i, ((_, leaf), std) in enumerate(zip(flat, stds)):
            if std is None:
                leaves.append(jnp.ones(leaf.shape, jnp.bfloat16))
            else:
                k = jax.random.fold_in(key, i)
                leaves.append((jax.random.normal(k, leaf.shape, jnp.bfloat16)
                               * jnp.bfloat16(std)))
        return jax.tree_util.tree_unflatten(treedef, leaves)

    return jax.jit(build)(jax.random.key(seed, impl="rbg"))


def run(ctx) -> Dict[str, Any]:
    return Server(ctx).window(ctx.traffic, ctx.seed, ctx.seconds)


class Server:
    """What a serving process sets up once: weights, engine, the logits
    check, the scheduler and every token-count shape.  ``window`` then
    serves one mix for one measured window; a run is one ``window``, the
    knee sweep several on the same server."""

    def __init__(self, ctx):
        import jax

        from deepspeed_tpu.inference.v2 import (InferenceEngineV2,
                                                RaggedInferenceEngineConfig)
        from deepspeed_tpu.observability import tracer as ds_tracer
        from deepspeed_tpu.serving import ContinuousBatchScheduler

        cfg, log = ctx.config, ctx.log
        family = spec.module("families", cfg["family"])
        reference = spec.module("reference", family.REFERENCE)
        serve = cfg["serve"]
        self.ctx = ctx
        self.shapes = family.shapes(cfg)
        self.vocab = int(cfg["vocab_size"])
        if len(ctx.devices) != 1:
            raise spec.SpecError("serve_ragged drives one chip; a tensor-"
                                 "parallel serving cell needs its own runner")

        params = make_params(family, cfg, ctx.seed)
        self.weight_bytes = sum(
            l.nbytes for l in jax.tree_util.tree_leaves(params))
        eng_cfg = RaggedInferenceEngineConfig.from_dict({
            "state_manager": {
                "max_ragged_batch_size": serve["token_budget"],
                "max_ragged_sequence_count":
                    serve["max_ragged_sequence_count"],
                "max_context": serve["max_context"]},
            "kv_cache": {"block_size": serve["block_size"],
                         "num_blocks": serve["kv_pool_blocks"]}})
        self.engine = engine = InferenceEngineV2(
            family.serve_model(cfg, int(serve["block_size"])), params,
            eng_cfg)
        jax.block_until_ready(engine.params)
        pool = serve["kv_pool_blocks"] * serve["block_size"]
        log(f"serve: weights {self.weight_bytes / 1e9:.2f} GB, KV pool "
            f"{serve['kv_pool_blocks']} x {serve['block_size']} tokens = "
            f"{pool * self.shapes['kv_bytes_per_token'] / 1e9:.2f}"
            f" GB, resident {device.resident_bytes(ctx.devices) / 1e9:.2f} GB")

        # -- correctness, outside the window -------------------------- #
        logit_err = _check_logits(engine, reference, family, cfg, ctx.seed,
                                  int(serve["check_prompt_tokens"]),
                                  int(serve["check_decode_tokens"]))
        self.correct = bool(logit_err <= LOGIT_TOL)
        log(f"serve: prefill+decode logits vs float32 reference: max |diff| "
            f"/ max |logit| = {logit_err:.4f} (tolerance {LOGIT_TOL})")

        self.trc = None
        if ctx.trace:
            # the program's own spans: tick phases on the Tracer, dispatch
            # brackets as profiler annotations.  Off in --trace 0 runs.
            self.trc = ds_tracer.Tracer(capacity=1 << 18)
            ds_tracer.enable_device_annotations(True)
        self.sched = ContinuousBatchScheduler(engine, tracer=self.trc)

        # -- warm-up: every token-count shape, alone and beside a decode  #
        mark = ctx.clock.mark()
        t0 = time.monotonic()
        _shape_ladder(self.sched, serve, self.vocab)
        ladder = ctx.clock.since(mark)
        log(f"serve: shape ladder {time.monotonic() - t0:.1f} s, "
            f"{ladder['programs']} programs built or read from the cache in "
            f"{ladder['seconds']:.1f} s")

    def window(self, mix: Dict[str, Any], seed: int,
               seconds: float) -> Dict[str, Any]:
        """Pre-roll, the measured window and the drain: one loop."""
        import jax

        from deepspeed_tpu.observability import tracer as ds_tracer
        from deepspeed_tpu.serving import SamplingParams

        ctx, log, sched, engine = self.ctx, self.ctx.log, self.sched, \
            self.engine
        clock = time.monotonic
        tracks: List[_Track] = []
        emitted = [0]

        def on_token(tr: _Track) -> None:
            tr.token_times.append(clock())
            emitted[0] += 1

        def submit(plan, due: float, in_window: bool) -> _Track:
            tr = _Track(plan, due, in_window)
            tr.submitted = clock()
            tracks.append(tr)
            tr.req = sched.submit(
                traffic.prompt_tokens(seed, plan, self.vocab),
                SamplingParams(greedy=True, max_new_tokens=plan.output_len),
                on_token=lambda _r, _t, _tr=tr: on_token(_tr))
            return tr

        preroll = float(mix.get("preroll_s", 0.0))
        drain_s = float(mix.get("drain_s", 30.0))
        closed = mix["kind"] == "closed_loop"
        if closed:
            source = traffic.ClosedLoop(mix, seed)
            busy: List[Optional[_Track]] = [None] * source.n
            free_at = [float(x) for x in source.first_due_s]
            plan_q: List[Any] = []
        else:
            plan_q = traffic.open_loop_plan(mix, seed, -preroll, seconds)
            log(f"serve: planned {traffic.summary(plan_q)}")
        nxt = 0
        trace_s = float(mix.get("trace_seconds", 4.0)) if ctx.trace else 0.0
        cap_cm = cap = None
        tick_ann = jax.profiler.TraceAnnotation if ctx.trace else None
        sm = engine.state_manager
        pool_blocks = sm.allocator.num_blocks - 1       # block 0 is reserved

        snap0 = sched.metrics.snapshot()
        t_begin = clock()
        t_start = t_begin + preroll             # the first measured instant
        t_end = t_start + seconds
        if closed:
            free_at = [t_begin + x for x in free_at]
        mark_pre = ctx.clock.mark()
        mark_win = None
        # per tick of the window: when it ended (s from the window's start),
        # how long it took, tokens it emitted, KV blocks held after it
        ticks: List[Tuple[float, float, int, int]] = []
        while True:
            now = clock()
            if mark_win is None and now >= t_start:
                pre = ctx.clock.since(mark_pre)
                log(f"serve: pre-roll {preroll:.1f} s built "
                    f"{pre['programs']} program(s)")
                mark_win = ctx.clock.mark()
            if ctx.trace and cap_cm is None and now >= t_end - trace_s:
                cap_cm = tracing.capture(os.path.join(ctx.out_dir, "trace"))
                cap = cap_cm.__enter__()
                now = clock()
            if now >= t_end:
                break
            if closed:
                for c in range(len(busy)):
                    if busy[c] is not None and busy[c].done:
                        free_at[c] = (busy[c].token_times or [now])[-1]
                        busy[c] = None
                    if busy[c] is None and free_at[c] <= now:
                        busy[c] = submit(source.next(c), free_at[c],
                                         free_at[c] >= t_start)
            else:
                while nxt < len(plan_q) and \
                        t_start + plan_q[nxt].due_s <= now:
                    p = plan_q[nxt]
                    submit(p, t_start + p.due_s, p.due_s >= 0.0)
                    nxt += 1
            if sched.num_pending:
                t0, n0 = clock(), emitted[0]
                if tick_ann is not None and cap is not None:
                    with tick_ann("bench/tick"):
                        sched.step()
                else:
                    sched.step()
                if mark_win is not None:
                    t1 = clock()
                    ticks.append((t1 - t_start, t1 - t0, emitted[0] - n0,
                                  pool_blocks - sm.free_blocks))
            elif not closed and nxt < len(plan_q):
                wait = min(t_start + plan_q[nxt].due_s, t_end) - clock()
                if wait > 0:
                    time.sleep(min(wait, 0.05))
            else:
                time.sleep(0.001)
        t_stop = clock()
        if mark_win is None:
            mark_win = ctx.clock.mark()
        built = ctx.clock.since(mark_win)
        if cap_cm is not None:
            cap_cm.__exit__(None, None, None)

        # -- drain: nothing new is submitted, except what was due inside
        # the window while its last tick ran (the generator was late, not
        # absent)
        while not closed and nxt < len(plan_q) and \
                plan_q[nxt].due_s < seconds:
            submit(plan_q[nxt], t_start + plan_q[nxt].due_s, True)
            nxt += 1
        wanted = [t for t in tracks if t.in_window]
        t_drain = clock()
        while any(not t.done for t in wanted) and sched.num_pending \
                and clock() - t_drain < drain_s:
            sched.step()
        drained_s = clock() - t_drain

        # -- the numbers ------------------------------------------------ #
        window_s = t_stop - t_start
        out_tokens = sum(1 for t in tracks for x in t.token_times
                         if t_start <= x < t_stop)
        in_tokens = stats.prompt_tokens_between(
            [(t.plan.prompt_len, t.submitted, t.token_times[0])
             for t in tracks if t.token_times], t_start, t_stop)
        ttft = [1e3 * (t.token_times[0] - t.due)
                for t in wanted if t.token_times]
        tpot = [1e3 * (t.token_times[-1] - t.token_times[0])
                / (len(t.token_times) - 1)
                for t in wanted if t.ok and len(t.token_times) > 1]
        itl = [1e3 * (b - a) for t in wanted if t.ok
               for a, b in zip(t.token_times, t.token_times[1:])]
        late = [1e3 * (t.submitted - t.due) for t in wanted]
        failed = [t for t in wanted if not t.ok]
        mid = t_start + window_s / 2
        unfinished_mid = sum(1 for t in tracks if t.submitted <= mid and
                             (not t.done or t.token_times[-1] > mid))
        unfinished_end = sum(1 for t in tracks if t.submitted <= t_stop and
                             (not t.done or t.token_times[-1] > t_stop))
        durs = [d for _, d, _, _ in ticks]
        busy_s = sum(durs)
        kv_live = (100.0 * sum(d * u for _, d, _, u in ticks)
                   / (busy_s * pool_blocks)) if busy_s else None
        kv_peak = max((u for *_, u in ticks), default=0)
        longest = sorted(ticks, key=lambda t: -t[1])[:3]
        snap = {k: int(v - snap0[k]) for k, v in
                sched.metrics.snapshot().items()
                if k in ("preemptions", "rejected")}
        log(f"serve: window {window_s:.2f} s, {len(wanted)} requests due, "
            f"{len(failed)} failed/unfinished after a {drained_s:.1f} s "
            f"drain, {out_tokens} tokens out and {in_tokens:.0f} prompt tokens "
            f"in, {len(ticks)} ticks, unfinished "
            f"at the middle {unfinished_mid} / at the end {unfinished_end}, "
            f"preemptions {snap['preemptions']}, rejected "
            f"{snap['rejected']}, generator late p90 "
            f"{stats.pct(late, 90) or 0:.2f} ms, {built['programs']} "
            f"program(s) built in the window")
        log(f"serve: token gap p50 {stats.pct(itl, 50) or 0:.3f} ms over "
            f"{len(itl)} gaps; tick p50 "
            f"{1e3 * (stats.pct(durs, 50) or 0):.1f} ms, p99 "
            f"{1e3 * (stats.pct(durs, 99) or 0):.1f} ms; the longest: " +
            ", ".join(f"{1e3 * d:.0f} ms ending at {at:.1f} s"
                      for at, d, _, _ in longest) +
            f"; KV blocks held: mean {kv_live or 0:.1f}% of "
            f"{pool_blocks}, peak {kv_peak}")
        _side_file(ctx, seed, {
            "window_s": window_s, "pool_blocks": pool_blocks,
            "ticks": ticks,
            "requests": [
                (t.plan.prompt_len, t.plan.output_len, t.due - t_start,
                 t.submitted - t_start,
                 (t.token_times[0] - t_start) if t.token_times else None,
                 (t.token_times[-1] - t_start) if t.token_times else None,
                 len(t.token_times), t.in_window) for t in tracks]})

        routes = _routes(engine) if ctx.trace else {}
        if routes:
            log(f"serve: attention route of each program: {routes}")
        facts: Dict[str, Any] = {
            "kind": "serve", "shapes": self.shapes,
            "weight_bytes": int(self.weight_bytes),
            "window_s": window_s, "ticks_window": len(ticks),
            "out_tok_s": out_tokens / window_s,
            "programs_built_window": built["programs"],
            "ttft_ms": ttft, "tpot_ms": tpot, "gen_late_ms": late,
            "kv_live_pct": kv_live,
            "unfinished_mid": unfinished_mid,
            "unfinished_end": unfinished_end,
            "preemptions": snap["preemptions"],
            "routes": routes,
            "t_start_ns": int(t_start * 1e9), "t_stop_ns": int(t_stop * 1e9),
            "tracks": [(t.plan.prompt_len, t.plan.output_len, t.submitted,
                        list(t.token_times)) for t in tracks],
        }
        if ctx.trace:
            facts.update({
                "capture": cap, "tracer_records": self.trc.records(),
                "resident_bytes": device.resident_bytes(ctx.devices),
                "step_temp_bytes": _largest_temp_bytes(engine)})
            ds_tracer.enable_device_annotations(False)
        e2e = {"total_tok_s": (in_tokens + out_tokens) / window_s,
               "ttft_p50_ms": stats.pct(ttft, 50),
               "tpot_p50_ms": stats.pct(tpot, 50),
               "itl_p50_ms": stats.pct(itl, 50)}
        return {"correct": self.correct, "attempted": len(wanted),
                "failed": len(failed), "t_window_start": t_start,
                "end_to_end": e2e, "facts": facts}


def _side_file(ctx, seed: int, record: Dict[str, Any]) -> None:
    """Every tick and request of the window, beside the run's output: what
    a stalled run is explained from, and what other window lengths are
    re-computed from."""
    with open(os.path.join(ctx.out_dir, f"window_seed{seed}.json"),
              "w") as f:
        json.dump(record, f)


def _check_logits(engine, reference, family, hf, seed: int, n_prompt: int,
                  n_decode: int) -> float:
    """One prompt (512 tokens in the cells) prefilled and a few given
    tokens (8) decoded through the engine (``put`` then ``decode_step``,
    the two paths the scheduler uses); logits at those positions against
    the reference's full forward over the same tokens."""
    import jax

    uid = 1 << 40
    ids = np.random.default_rng([seed, 99]).integers(
        0, int(hf["vocab_size"]), size=(n_prompt + n_decode,))
    got = [np.asarray(engine.put([uid], [ids[:n_prompt].tolist()])[uid],
                      np.float32)]
    for t in ids[n_prompt:]:
        row = engine.decode_step([uid], [int(t)])
        got.append(np.asarray(jax.device_get(row), np.float32)[0])
    engine.flush([uid])
    got = np.stack(got)
    want = reference.logits_at(
        family.reference_params(engine.params), ids, hf,
        rows=list(range(n_prompt - 1, n_prompt + n_decode)))
    if got.shape != want.shape or not np.isfinite(got).all():
        return float("inf")
    return float(np.max(np.abs(got - want)) / np.max(np.abs(want)))


def _shape_ladder(sched, serve: Dict[str, Any], vocab: int) -> None:
    """Serve prompts of 8, 16, ... token_budget tokens, each once alone (a
    prefill-only tick) and once while another sequence decodes (a mixed
    tick), through the scheduler.  The engine pads a tick's token count to
    a bucket; the ladder's counts land in every bucket either way, and the
    decoding companion makes the pure-decode program."""
    from deepspeed_tpu.serving import SamplingParams

    rng = np.random.default_rng(7)
    budget = int(serve["token_budget"])
    lens = [n for n in (8 << i for i in range(20)) if n < budget] + [budget]
    cap = int(serve["max_context"]) - 2

    def prompt(n):
        return rng.integers(0, vocab, size=(min(n, cap),)).tolist()

    for n in lens:                                  # alone
        sched.submit(prompt(n), SamplingParams(greedy=True, max_new_tokens=1))
        sched.run_until_idle()
    companion = sched.submit(prompt(8), SamplingParams(
        greedy=True, max_new_tokens=2 * len(lens) + 8))
    while not companion.generated:
        sched.step()
    for n in lens:                                  # beside a decode
        sched.submit(prompt(n), SamplingParams(greedy=True, max_new_tokens=1))
        sched.step()
        sched.step()
    sched.run_until_idle()


def _routes(engine) -> Dict[str, Dict[str, int]]:
    """Which attention kernel each program the run built calls, from its
    lowered text (no Mosaic call = the XLA composition, e.g. the dense pool
    read of pure-decode ticks on a tight pool)."""
    out = {}
    for key in engine.step_keys:
        name = "decode_step" if key == ("decode_step",) else \
            f"T{key[0]}" + ("_tiled" if len(key) > 1 and key[1] else "")
        out[name] = device.mosaic_kernels(
            engine.lower_step(key).as_text()) or {"xla": 1}
    return out


def _largest_temp_bytes(engine) -> int:
    """The largest temporaries of any program the run built, from XLA's
    memory analysis of each."""
    worst = 0
    for key in engine.step_keys:
        mem = engine.lower_step(key).compile().memory_analysis()
        worst = max(worst, int(getattr(mem, "temp_size_in_bytes", 0)))
    return worst
