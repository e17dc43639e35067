"""Training runner: ``deepspeed_tpu.initialize`` -> ``engine(batch)`` /
``engine.backward`` / ``engine.step``, exactly the calls a user makes
(as ``chip_smoke.py`` does), on the mesh the configuration states.

Set-up: mesh, engine, weights born sharded on the device in one jitted init
from ``--seed``; the step-0 loss against the plain float32 reference on the
same weights and batch; three steps on that one batch, whose loss must fall
each time (backward and optimizer have the right sign and scale) and which
compile the cell's one step program.  Window: fresh seeded batches, steps
dispatched in groups of about a second with one ``block_until_ready`` and
loss fetch per group, so the clock is read only when the device has
finished.  ``train_tok_s_chip`` is the tokens of one group over the MEDIAN
group time: the window holds some fifty groups, and a second the machine
lost once (seen on the chip: 1.9 s in one run of twelve, PERF.md PR 22)
does not move a median.  Every group's seconds go to the run's side file
and the slowest group is printed against the median.
"""

from __future__ import annotations

import json
import os
import time
from typing import Any, Dict, List

import numpy as np

from benchmark.lib import device, spec, tracing, traffic

# Step-0 loss, the program's bf16 step against the float32 reference on the
# same (bf16-rounded) weights and batch.  At seeded init the loss sits near
# ln(vocab) ~ 10.9-11.3; it is a mean of thousands of float32 cross-entropies
# whose bf16 activation roundings (2^-8 relative per value, independent)
# average out.  Measured on the chip over 20 runs and seeds of both training
# cells: 1.3e-5 .. 4.7e-4 (PERF.md, PR 22).  2e-3 is four times the largest
# seen and nothing more: an int8 or fp8 matmul path perturbs the logits by
# percents, and a wrong mask, position or scale moves the loss by over 1e-2.
LOSS_TOL = 2e-3
# every loss in the window stays below step-0 loss + this (random tokens:
# the loss has nowhere to go but ~ln(vocab); a diverging optimizer leaves)
LOSS_BAND = 0.5


def _ds_config(train: Dict[str, Any]) -> Dict[str, Any]:
    return {
        "train_micro_batch_size_per_gpu": train["micro_batch_per_replica"],
        "gradient_accumulation_steps": train["gradient_accumulation_steps"],
        "optimizer": {"type": train["optimizer"],
                      "params": {"lr": train["lr"]}},
        "zero_optimization": {"stage": train["zero_stage"]},
        "bf16": {"enabled": True},
        "gradient_clipping": train["gradient_clipping"],
        "steps_per_print": train["steps_per_print"],
    }


def run(ctx) -> Dict[str, Any]:
    import jax

    import deepspeed_tpu
    from deepspeed_tpu.parallel import groups

    cfg, mix, log = ctx.config, ctx.traffic, ctx.log
    family = spec.module("families", cfg["family"])
    reference = spec.module("reference", family.REFERENCE)
    hf, train = cfg, cfg["train"]
    shapes = family.shapes(hf)
    tp, dp = int(train["mesh"]["model"]), int(train["mesh"]["data"])
    if tp * dp != len(ctx.devices):
        raise spec.SpecError(f"mesh {train['mesh']} needs {tp * dp} devices, "
                             f"the cell has {len(ctx.devices)}")
    batch, seq = int(mix["global_batch"]), int(mix["seq_len"])
    if batch != dp * int(train["micro_batch_per_replica"]):
        raise spec.SpecError("traffic global_batch != replicas x micro batch")

    groups.reset()
    topo = groups.initialize_mesh(model_parallel_size=tp,
                                  data_parallel_size=dp, devices=ctx.devices)
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=family.train_model(hf), config=_ds_config(train), topology=topo)
    batches = traffic.train_batches(mix, ctx.seed, int(hf["vocab_size"]))
    first = next(batches)
    engine.initialize_parameters(first, first, seed=ctx.seed)
    n_params = sum(int(np.prod(l.shape)) for l in
                   jax.tree_util.tree_leaves(engine.state["master"]))
    log(f"train: {n_params / 1e6:.1f}M parameters, mesh data={dp} model={tp}, "
        f"zero stage {engine.zero_stage}, {batch}x{seq} tokens/step")

    # -- correctness, outside the window ------------------------------ #
    ref_loss = reference.loss(family.reference_params(engine.state["params"]),
                              first, hf)

    def step(ids):
        loss = engine(ids, ids)
        engine.backward(loss)
        engine.step()
        return loss

    t0 = time.monotonic()
    warm = [float(step(first)) for _ in range(3)]
    warm_s = time.monotonic() - t0
    t0 = time.monotonic()
    float(step(first))
    step_s = max(time.monotonic() - t0, 1e-4)
    loss_err = abs(warm[0] - ref_loss)
    correct = bool(np.isfinite(warm).all() and loss_err <= LOSS_TOL
                   and warm[0] > warm[1] > warm[2])
    log(f"train: step-0 loss {warm[0]:.5f} vs float32 reference "
        f"{ref_loss:.5f} (|diff| {loss_err:.2e}, tolerance {LOSS_TOL}); "
        f"same batch three times: {[round(x, 4) for x in warm]}; "
        f"warm-up {warm_s:.1f} s, one step {step_s * 1e3:.1f} ms")

    group = max(1, int(round(float(mix.get("group_seconds", 1.0)) / step_s)))
    trace_steps = int(mix.get("trace_steps", 8))
    facts: Dict[str, Any] = {
        "kind": "train", "shapes": shapes, "seq": seq, "batch": batch,
        "chips": len(ctx.devices), "n_params": n_params}

    # -- the window --------------------------------------------------- #
    losses: List[float] = []
    group_s: List[float] = []
    mark = ctx.clock.mark()
    t_start = time.monotonic()
    elapsed = 0.0
    while elapsed < ctx.seconds:
        pending = [step(next(batches)) for _ in range(group)]
        jax.block_until_ready(pending)
        losses += [float(x) for x in pending]
        now = time.monotonic() - t_start
        group_s.append(now - elapsed)
        elapsed = now
    built = ctx.clock.since(mark)
    steps = group * len(group_s)
    tokens_per_s = group * batch * seq / float(np.median(group_s))
    tok_s_chip = tokens_per_s / len(ctx.devices)
    facts.update({"window_s": elapsed, "steps": steps,
                  "programs_built_window": built["programs"],
                  "tokens_per_s": tokens_per_s})

    # -- a short traced stretch, after the window (--trace 1 only) ----- #
    if ctx.trace:
        with tracing.capture(os.path.join(ctx.out_dir, "trace")) as cap:
            for _ in range(trace_steps):
                with jax.profiler.TraceAnnotation("bench/make_batch"):
                    ids = next(batches)
                with jax.profiler.TraceAnnotation("bench/dispatch_step"):
                    loss = step(ids)
                losses.append(loss)
            with jax.profiler.TraceAnnotation("bench/wait_steps"):
                jax.block_until_ready(losses[-1])
        losses[-trace_steps:] = [float(x) for x in losses[-trace_steps:]]
        facts.update({"capture": cap, "traced_steps": trace_steps})
        facts["attention_route"] = device.mosaic_kernels(
            engine.lower_train_step().as_text())
        log(f"train: attention kernels in the lowered step: "
            f"{facts['attention_route']}")
        facts["step_temp_bytes"] = _step_temp_bytes(engine)
        facts["resident_bytes"] = device.resident_bytes(ctx.devices)

    bad = [x for x in losses
           if not np.isfinite(x) or x > warm[0] + LOSS_BAND]
    with open(os.path.join(ctx.out_dir, f"losses_seed{ctx.seed}.json"),
              "w") as f:
        json.dump({"warmup": warm, "reference_step0": ref_loss,
                   "window": losses, "group_steps": group,
                   "group_seconds": group_s}, f)
    log(f"train: {steps} steps in {elapsed:.2f} s (groups of {group}: median "
        f"{np.median(group_s):.4f} s, slowest {max(group_s):.4f} s, the "
        f"{int(np.argmax(group_s)) + 1}th of {len(group_s)}; over the whole "
        f"window {steps * batch * seq / elapsed / len(ctx.devices):.1f} "
        f"tokens/s/chip), {built['programs']} program(s) built in the "
        f"window, loss {losses[0]:.4f} .. {losses[-1]:.4f}")
    groups.reset()
    return {"correct": correct and not bad, "attempted": len(losses),
            "failed": len(bad), "t_window_start": t_start,
            "end_to_end": {"train_tok_s_chip": tok_s_chip}, "facts": facts}


def _step_temp_bytes(engine) -> int:
    """Temporaries of the compiled step program, from XLA's own memory
    analysis (``memory_stats()`` does not see them on this runtime)."""
    mem = engine.lower_train_step().compile().memory_analysis()
    return int(getattr(mem, "temp_size_in_bytes", 0))
