"""Benchmark: training throughput of GPT-2-125M-class Llama on one chip.

Prints ONE JSON line: tokens/sec/chip plus model FLOPs utilisation.
``vs_baseline`` compares achieved MFU against the reference's published
sustained utilisation (>54% of peak on A100, blogs/deepspeed-ulysses — see
BASELINE.md): vs_baseline = our_mfu / 0.54.
"""

from __future__ import annotations

import json
import sys
import time

import numpy as np

from deepspeed_tpu.utils.compile_cache import enable_compile_cache
from deepspeed_tpu.utils.platform import require_tpu


def peak_flops_per_chip() -> float:
    import jax

    # the per-chip NUMBERS live in one table (observability.roofline
    # CHIP_SPECS — perf_report reads the same one)
    from deepspeed_tpu.observability.roofline import chip_specs

    return chip_specs(jax.devices()[0].device_kind.lower())[0]


def _build_train(heads: int, micro_batch: int, seq: int,
                 attention_layout: str):
    """One warm train-step closure at the given geometry/layout:
    returns (engine, step, hard_sync, batch, n_dev, vocab)."""
    import jax
    import jax.numpy as jnp

    import deepspeed_tpu
    from deepspeed_tpu.models import LlamaConfig, LlamaForCausalLM

    cfg_m = LlamaConfig(vocab_size=32000, hidden_size=768,
                        intermediate_size=2048, num_hidden_layers=12,
                        num_attention_heads=heads, num_key_value_heads=heads,
                        max_position_embeddings=2048, dtype=jnp.bfloat16)
    ds_config = {
        "train_micro_batch_size_per_gpu": micro_batch,
        "gradient_accumulation_steps": 1,
        "optimizer": {"type": "AdamW", "params": {"lr": 1e-4}},
        "zero_optimization": {"stage": 1},
        "bf16": {"enabled": True},
        "gradient_clipping": 1.0,
        # "folded" = layout-native attention ([B,S,H*D] end to end, no
        # BSHD<->BHSD transposes); "paired" additionally packs d<128
        # heads into lane-full MXU tiles — exercises the runtime-config
        # plumbing either way
        "attention_layout": attention_layout,
    }
    engine, _, _, _ = deepspeed_tpu.initialize(model=LlamaForCausalLM(cfg_m),
                                               config=ds_config)
    n_dev = engine.dp_world_size
    batch = micro_batch * n_dev
    rng = np.random.default_rng(0)
    ids = rng.integers(0, cfg_m.vocab_size, size=(batch, seq)).astype(np.int32)

    def step():
        loss = engine(ids, ids)
        engine.backward(loss)
        engine.step()
        return loss

    def hard_sync():
        """Wait for every dispatched step: the timed window ends when the
        final parameter update is on the device."""
        jax.block_until_ready(engine.state["master"])

    return engine, step, hard_sync, batch, n_dev, cfg_m


def _measure(heads: int, micro_batch: int, seq: int,
             attention_layout: str = "bshd", ledger_out: dict = None):
    """One training-throughput measurement at the given head geometry.
    Returns (tokens/s/chip, mfu, loss, step_ms, n_params, n_dev).
    With ``ledger_out`` (a dict), the engine's compiled train programs'
    HLO memory/cost analysis is recorded into it (explicit
    ``unavailable`` on failure) — the BENCH JSON's memory evidence."""
    import jax

    engine, step, hard_sync, batch, n_dev, cfg_m = _build_train(
        heads, micro_batch, seq, attention_layout)

    # warmup + compile
    for _ in range(3):
        loss = step()
    hard_sync()

    iters = 20
    t0 = time.perf_counter()
    for _ in range(iters):
        loss = step()
    hard_sync()
    dt = time.perf_counter() - t0

    tokens_per_sec_per_chip = batch * seq * iters / dt / n_dev

    if ledger_out is not None:
        from deepspeed_tpu.observability.memory import unavailable_entry

        # compile-time HLO memory evidence for the program just timed
        # (re-lowered from recorded shapes; the persistent compilation
        # cache makes it a lookup, not a second cold compile)
        try:
            ledger_out.update(
                engine.capture_memory_ledger().to_json()["entries"])
        except Exception as e:  # noqa: BLE001 — absence is a record
            ledger_out["train_step"] = unavailable_entry(
                f"{type(e).__name__}: {e}")

    from deepspeed_tpu.utils.tensors import tree_num_params

    n_params = tree_num_params(engine.state["master"])
    # 6ND fwd+bwd model FLOPs (+ attention term)
    att_flops = (12 * cfg_m.num_hidden_layers * cfg_m.hidden_size * seq) / \
        (6 * n_params)
    flops_per_token = 6 * n_params * (1 + att_flops)
    mfu = tokens_per_sec_per_chip * flops_per_token / peak_flops_per_chip()
    return (tokens_per_sec_per_chip, mfu, float(jax.device_get(loss)),
            1000 * dt / iters, n_params, n_dev)


def measure_paired_ab(heads: int = 12, micro_batch: int = 8,
                      seq: int = 1024, windows: int = 5,
                      iters_per_window: int = 4) -> dict:
    """Paired-vs-folded attention A/B on the honest 12-head/d64
    geometry, INTERLEAVED per the perf_gate methodology: both arms'
    engines are built and warmed first, then timed in alternating
    windows (F P F P ...) — two sequential single-arm windows each
    self-report a clean intra-window noise floor yet drift wholesale
    when host load shifts between them (PERFLOG r16).  Reports the
    per-arm median-of-window step times, the paired/folded ratio, and
    the cross-window ratio spread as the record's ``noise_pct``."""
    import math

    arms = ("folded", "paired")
    steps, syncs = {}, {}
    for layout in arms:
        _, step, hard_sync, _, _, _ = _build_train(
            heads, micro_batch, seq, layout)
        for _ in range(3):          # warm + compile both arms up front
            step()
        hard_sync()
        steps[layout], syncs[layout] = step, hard_sync
    times = {a: [] for a in arms}
    for _ in range(windows):
        for layout in arms:
            t0 = time.perf_counter()
            for _ in range(iters_per_window):
                steps[layout]()
            syncs[layout]()
            times[layout].append(
                (time.perf_counter() - t0) / iters_per_window)
    med = {a: float(np.median(times[a])) for a in arms}
    ratios = [p / f for p, f in zip(times["paired"], times["folded"])]
    ratio = float(np.median(ratios))
    noise_pct = 100.0 * (max(ratios) - min(ratios)) / 2.0 \
        if len(ratios) > 1 else 0.0
    if not all(math.isfinite(med[a]) and med[a] > 0 for a in arms):
        raise RuntimeError(f"paired A/B produced degenerate timings {med}")
    return {
        "heads": heads, "head_dim": 768 // heads,
        "micro_batch": micro_batch, "seq": seq,
        "interleaved_windows": windows,
        "iters_per_window": iters_per_window,
        "folded": {"step_time_ms": round(1000 * med["folded"], 3)},
        "paired": {"step_time_ms": round(1000 * med["paired"], 3)},
        # < 1.0 = paired beat folded on this host/chip
        "ratio_vs_folded": round(ratio, 4),
        "noise_pct": round(noise_pct, 2),
    }


def measure_offload_pipelined_ab(buffer_count: int = 8,
                                 windows: int = 6,
                                 iters_per_window: int = 4,
                                 fp16: bool = False) -> dict:
    """Pipelined-vs-synchronous optimizer-offload A/B, interleaved per
    the perf_gate methodology (S P S P ... windows, median-of-window
    step times, cross-window ratio spread as ``noise_pct``).

    Runs on the single-device :class:`MiniOffloadEngine` twin — the
    engine's OWN ``_pipelined_offload_step``/``_offload_transfer``
    methods over a one-device mesh — so the A/B is measurable on any
    host.  On TPU the host tier is real ``pinned_host`` memory; on a
    CPU host launched via ``--offload-ab`` a second virtual CPU device
    stands in (real inter-device copies); otherwise transfers degrade
    to same-device no-ops and only the program-split cost is measured
    (the record says which via ``host_tier``)."""
    import math

    from deepspeed_tpu.runtime.zero.offload_twin import MiniOffloadEngine

    arms = {"sync": MiniOffloadEngine(pipeline=False, fp16=fp16, seed=0),
            "pipelined": MiniOffloadEngine(pipeline=True,
                                           buffer_count=buffer_count,
                                           fp16=fp16, seed=0)}
    for eng in arms.values():
        for _ in range(3):          # warm + compile both arms up front
            eng.step()
        eng.sync()
    times = {a: [] for a in arms}
    for _ in range(windows):
        for a, eng in arms.items():
            t0 = time.perf_counter()
            for _ in range(iters_per_window):
                eng.step()
            eng.sync()
            times[a].append((time.perf_counter() - t0) / iters_per_window)
    med = {a: float(np.median(times[a])) for a in arms}
    ratios = [p / s for p, s in zip(times["pipelined"], times["sync"])]
    ratio = float(np.median(ratios))
    noise_pct = 100.0 * (max(ratios) - min(ratios)) / 2.0 \
        if len(ratios) > 1 else 0.0
    if not all(math.isfinite(med[a]) and med[a] > 0 for a in arms):
        raise RuntimeError(f"offload A/B produced degenerate timings {med}")
    stats = arms["pipelined"]._offload_stats.snapshot()
    return {
        "n_params": arms["sync"].n_params,
        "buffer_count": buffer_count,
        "host_tier": arms["pipelined"].host_tier,
        "fp16": bool(fp16),
        "interleaved_windows": windows,
        "iters_per_window": iters_per_window,
        "sync": {"step_time_ms": round(1000 * med["sync"], 3)},
        "pipelined": {"step_time_ms": round(1000 * med["pipelined"], 3)},
        # < 1.0 = pipelined beat the synchronous whole-tree boundary
        "ratio_vs_sync": round(ratio, 4),
        "noise_pct": round(noise_pct, 2),
        "overlap_fraction": round(
            stats["observability/offload_overlap_fraction"], 4),
        "transfer_buckets": stats["observability/offload_buckets"],
    }


def _cli_trace_out():
    """``--trace OUT``: bracket the bench's stages in host spans (and
    turn on jax.profiler TraceAnnotations around engine dispatch) and
    write a Chrome/Perfetto timeline to OUT next to the JSON record —
    the merged host↔device view ROADMAP item 2's remat/fusion work
    profiles against when a ``jax.profiler`` capture runs alongside."""
    for i, a in enumerate(sys.argv):
        if a.startswith("--trace="):
            return a.split("=", 1)[1]
        if a == "--trace" and i + 1 < len(sys.argv):
            return sys.argv[i + 1]
    return None


def main(devs):
    t_start = time.perf_counter()
    trace_out = _cli_trace_out()
    tracer = None
    if trace_out is not None:
        from deepspeed_tpu.observability import (Tracer,
                                                 enable_device_annotations)

        enable_device_annotations(True)
        tracer = Tracer(capacity=65536, tid="bench")

    def _stage(name):
        import contextlib

        if tracer is None:
            return contextlib.nullcontext()
        return tracer.span(name, trace_id=_bench_trace_id)

    _bench_trace_id = None
    if tracer is not None:
        from deepspeed_tpu.observability import mint_trace_id

        _bench_trace_id = mint_trace_id()

    def elapsed():
        return time.perf_counter() - t_start

    # --- 7B int8 serving (the north-star-scale proof, driver-captured).
    # Runs FIRST so a slow training compile can never push it past the
    # ~600 s driver budget.  A phase that raises fails the whole bench.
    from bench_serving import measure_7b

    with _stage("bench/7b_serving"):
        serving_7b = measure_7b()
    serving_7b["wall_s"] = round(elapsed(), 1)
    print(f"# 7b serving done at {elapsed():.0f}s", file=sys.stderr)

    seq = 1024
    # HEADLINE metric: the original GPT-2-125M geometry so vs_baseline
    # stays comparable across rounds against the fixed 0.54-MFU
    # reference bar.
    HEADLINE_HEADS, HEADLINE_MB = 12, 8
    # Secondary: the TPU-first geometry (head_dim=128 fills the 128-wide
    # MXU/vector lanes; same params, hidden size and model FLOPs) at the
    # throughput-optimal micro-batch — reported separately, NOT in the
    # headline, so geometry changes can never inflate vs_baseline.
    TPU_HEADS, TPU_MB = 6, 16
    # Headline attention layout: DS_ATTENTION_LAYOUT=folded routes the
    # honest geometry through the layout-native kernels; default "bshd"
    # keeps the headline exactly comparable to prior rounds.
    import os

    headline_layout = os.environ.get("DS_ATTENTION_LAYOUT", "bshd")
    mem_entries = {}
    with _stage("bench/headline_train"):
        tok_s, mfu, loss, step_ms, n_params, n_dev = _measure(
            heads=HEADLINE_HEADS, micro_batch=HEADLINE_MB, seq=seq,
            attention_layout=headline_layout, ledger_out=mem_entries)

    # on-chip Pallas kernel selftest (every kernel vs its jnp reference,
    # compiled — not interpret mode), time-permitting
    print(f"# headline training done at {elapsed():.0f}s", file=sys.stderr)
    if elapsed() < 400:
        import os

        sys.path.insert(0, os.path.join(
            os.path.dirname(os.path.abspath(__file__)), "tools"))
        from kernel_selftest import run_selftest

        selftest = run_selftest()
    else:
        selftest = {"ok": False, "note": "skipped: bench time budget"}

    tpu_geom = None
    if elapsed() < 430:
        with _stage("bench/tpu_geometry"):
            tok_s2, mfu2, _loss2, step_ms2, _, _ = _measure(
                heads=TPU_HEADS, micro_batch=TPU_MB, seq=seq)
        tpu_geom = {
            "heads": TPU_HEADS, "head_dim": 768 // TPU_HEADS,
            "micro_batch": TPU_MB,
            "tokens_per_sec_per_chip": round(tok_s2, 1),
            "mfu": round(mfu2, 4),
            "step_time_ms": round(step_ms2, 2),
        }

    # A/B for the layout-native path: the honest geometry with the folded
    # attention layout (same JSON shape as the headline extras), so one
    # bench run yields the before/after the PERFLOG needs. Runs LAST so
    # it can never crowd out the long-standing tpu_geometry record.
    folded_geom = None
    if headline_layout != "folded":
        if elapsed() < 480:
            tok_sf, mfuf, _lossf, step_msf, _, _ = _measure(
                heads=HEADLINE_HEADS, micro_batch=HEADLINE_MB, seq=seq,
                attention_layout="folded")
            folded_geom = {
                "heads": HEADLINE_HEADS,
                "head_dim": 768 // HEADLINE_HEADS,
                "micro_batch": HEADLINE_MB,
                "tokens_per_sec_per_chip": round(tok_sf, 1),
                "mfu": round(mfuf, 4),
                "step_time_ms": round(step_msf, 2),
            }
            print(f"# folded-layout A/B done at {elapsed():.0f}s",
                  file=sys.stderr)
        else:
            folded_geom = {"note": "skipped: bench time budget"}

    # Paired-vs-folded A/B on the honest d64 geometry (ROADMAP item 2's
    # head-pairing fix): interleaved arms per the perf_gate methodology,
    # budget-guarded like the folded A/B above.
    paired_ab = None
    if elapsed() < 500:
        with _stage("bench/paired_ab"):
            paired_ab = measure_paired_ab(
                heads=HEADLINE_HEADS, micro_batch=HEADLINE_MB, seq=seq)
        print(f"# paired-layout A/B done at {elapsed():.0f}s",
              file=sys.stderr)
    else:
        paired_ab = {"note": "skipped: bench time budget"}

    # The pipelined-vs-sync optimizer-offload A/B is its own invocation
    # (``bench.py --offload-ab``): this process holds the chip, and a
    # child that needs it would fail or hang.

    # --- HLO memory ledger: the 7B ZeRO-3 VIRTUAL-MESH compile evidence
    # (ROADMAP item 3) — abstract lowering in a CPU subprocess (no
    # weights materialised, the parent's TPU backend untouched), bounded
    # by the remaining bench budget.  The BENCH JSON always carries the
    # entry: real memory_analysis numbers, or an explicit unavailable
    # record naming why (timeout / budget).
    _7b_key = "virtual_mesh/7b_zero3"
    from deepspeed_tpu.observability.memory import unavailable_entry
    try:
        from deepspeed_tpu.observability.memory import (
            virtual_mesh_probe_subprocess)

        budget_left = 560 - elapsed()
        if budget_left > 60:
            with _stage("bench/memory_ledger_7b_zero3"):
                mem_entries[_7b_key] = virtual_mesh_probe_subprocess(
                    "7b_zero3", timeout_s=min(240.0, budget_left))
        else:
            mem_entries[_7b_key] = unavailable_entry(
                "skipped: bench time budget")
    except Exception as e:  # noqa: BLE001 — absence is a record
        mem_entries[_7b_key] = unavailable_entry(
            f"{type(e).__name__}: {e}")
    print(f"# memory ledger done at {elapsed():.0f}s", file=sys.stderr)

    if tracer is not None:
        from deepspeed_tpu.observability import write_chrome_trace

        write_chrome_trace(trace_out, tracer.export_events())
        print(f"# trace written to {trace_out}", file=sys.stderr)
    print(json.dumps({
        "metric": "train_tokens_per_sec_per_chip_gpt125m",
        "value": round(tok_s, 1),
        "unit": "tokens/s/chip",
        "vs_baseline": round(mfu / 0.54, 4),
        "extra": {
            "mfu": round(mfu, 4),
            "loss": loss,
            "params_m": round(n_params / 1e6, 1),
            "seq": seq, "batch": HEADLINE_MB * n_dev, "n_devices": n_dev,
            "step_time_ms": round(step_ms, 2),
            "heads": HEADLINE_HEADS,
            "head_dim": 768 // HEADLINE_HEADS,
            "micro_batch": HEADLINE_MB,
            "attention_layout": headline_layout,
            # ZeRO comm-row inputs for perf_report's waterfall (the
            # bench config above: stage 1, engine overlap default on)
            "zero_stage": 1,
            "overlap_comm": True,
            # geometry constants so perf_report's cost model needs no
            # out-of-band knowledge of the bench config
            "geometry": {"hidden": 768, "layers": 12,
                         "intermediate": 2048, "vocab": 32000,
                         "dtype": "bfloat16"},
            "memory_ledger": {"schema": "ds-memory-ledger-v1",
                              "entries": mem_entries},
            **({"folded_attention": folded_geom} if folded_geom else {}),
            **({"paired_attention": paired_ab} if paired_ab else {}),
            **({"tpu_geometry": tpu_geom} if tpu_geom else {}),
            "serving_7b": serving_7b,
            "kernel_selftest": selftest,
            "platform": devs[0].platform,
            "bench_wall_s": round(elapsed(), 1),
        },
    }))


if __name__ == "__main__":
    # every mode needs the chip, and any failure is a non-zero exit with
    # a traceback — never an error record under the metric's name
    _devs = require_tpu("bench")
    enable_compile_cache()
    if "--offload-ab" in sys.argv:
        # standalone pipelined-vs-sync offload microbench: one JSON
        # record in the perf_gate shape (tools/perf_gate.py
        # train_offload_pipelined_ab spec gates value + ratio_vs_sync,
        # margin widened by the record's own noise_pct)
        ab = measure_offload_pipelined_ab(fp16="--fp16" in sys.argv)
        print(json.dumps({
            "metric": "train_offload_pipelined_ab",
            "value": ab["pipelined"]["step_time_ms"],
            "unit": "ms/step",
            "vs_baseline": ab["ratio_vs_sync"],
            "extra": ab,
        }))
    elif "--paired-ab" in sys.argv:
        # standalone paired-vs-folded train microbench: one JSON record
        # in the perf_gate shape (tools/perf_gate.py
        # train_paired_attention_ab spec gates value + ratio, margin
        # widened by the record's own interleaved-arm noise_pct)
        ab = measure_paired_ab()
        print(json.dumps({
            "metric": "train_paired_attention_ab",
            "value": ab["paired"]["step_time_ms"],
            "unit": "ms/step",
            "vs_baseline": ab["ratio_vs_folded"],
            "extra": ab,
        }))
    else:
        main(_devs)
