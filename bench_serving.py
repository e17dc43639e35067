"""Serving benchmark: FastGen ragged Llama (125M-class, GQA) on one chip.

Methodology follows the reference's FastGen benchmark framing
(blogs/deepspeed-fastgen/README.md:139-168): N concurrent clients submit
prompts, we record per-client TTFT (prompt submitted -> first token out,
prefill through the SplitFuse ragged engine) and the steady-state decode
throughput with all clients batched continuously.

Model geometry is the GQA serving shape modern targets use (Mistral-style
3:1 query:kv head ratio) in bf16 — the dtype/geometry the roofline
denominator is computed from, so the ratio is self-consistent.

Steady-state decode rate uses a two-point measurement: the same decode
program is run for n1 and n2 steps (each timed wall-clock including its
single host sync) and the marginal per-step time is (t2-t1)/(n2-n1).
This isolates the framework's per-token cost from the fixed cost of the
one blocking sync that ends each run. Wall-clock rates are reported
alongside in ``extra``. The per-step put()-path rate is measured
the same two-point way over ``decode_step`` — the put scheduling path
(host-side KV allocation + metadata build every step) with device-resident
token feedback.

Prints ONE JSON line shaped like bench.py's. ``vs_baseline`` compares the
steady-state decode tokens/s against HALF the single-chip HBM roofline for
batched decode (each decode step must stream all model weights once per
ragged batch: roofline tok/s = clients * BW / model_bytes; sustaining
>=50% of a memory roofline is the same bar the reference's >=54%-of-peak
training claim sets for compute).
"""

from __future__ import annotations

import json
import sys
import time

import numpy as np


def hbm_bandwidth_bytes_per_s() -> float:
    """The chip's HBM bandwidth for every roofline here — the NUMBERS
    live in observability.roofline's CHIP_SPECS (perf_report reads the
    same table)."""
    import jax

    from deepspeed_tpu.observability.roofline import chip_specs

    kind = jax.devices()[0].device_kind.lower()
    return chip_specs("" if "cpu" in kind else kind)[1]


def main():
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.inference.v2 import (InferenceEngineV2,
                                            RaggedInferenceEngineConfig)
    from deepspeed_tpu.inference.v2.model_implementations import RaggedLlama
    from deepspeed_tpu.models import LlamaConfig, LlamaForCausalLM

    # 125M-class Llama, GQA serving geometry (6 q heads : 2 kv heads)
    cfg = LlamaConfig(vocab_size=32000, hidden_size=768,
                      intermediate_size=2048, num_hidden_layers=12,
                      num_attention_heads=6, num_key_value_heads=2,
                      max_position_embeddings=2048, dtype=jnp.bfloat16)
    clients = 8
    prompt_len = 256
    gen_tokens = 64
    warm_tokens = 16
    block_size = 128

    params = LlamaForCausalLM(cfg).init(
        jax.random.key(0), np.zeros((1, 8), np.int32))["params"]
    params = jax.tree.map(lambda p: p.astype(jnp.bfloat16), params)

    max_ctx = prompt_len + 1 + 2 * (warm_tokens + gen_tokens) + 8
    eng_cfg = RaggedInferenceEngineConfig.from_dict({
        "state_manager": {"max_ragged_batch_size": 512,
                          "max_ragged_sequence_count": clients,
                          "max_context": max_ctx},
        "kv_cache": {"block_size": block_size},
    })
    engine = InferenceEngineV2(RaggedLlama(cfg, block_size), params, eng_cfg)

    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, size=(prompt_len,)).tolist()
               for _ in range(clients)]
    uids = list(range(clients))

    # warmup: compile prefill + decode_loop chunks + decode_step programs
    # at exactly the shapes the measured loops use (8 live sequences)
    wuids = list(range(100, 100 + clients))
    engine.put(wuids, [prompts[i][:8] for i in range(clients)])
    engine.put([wuids[0]], [prompts[0]])
    engine.decode_loop(wuids, [1] * clients, warm_tokens)
    engine.decode_loop(wuids, [1] * clients, gen_tokens)
    lg, nx = engine.decode_step(wuids, [1] * clients, greedy=True)
    lg, nx = engine.decode_step(wuids, nx, greedy=True)
    jax.block_until_ready(lg)
    engine.flush(wuids)

    # --- TTFT: submit each client's prompt, time to its first token.
    # put() device_gets the logits, so wall-clock here is real device time.
    ttft_ms = []
    for uid in uids:
        t0 = time.perf_counter()
        logits = engine.put([uid], [prompts[uid]])
        int(np.argmax(logits[uid]))  # first token materialised on host
        ttft_ms.append((time.perf_counter() - t0) * 1000)
    engine.flush(uids)

    # --- steady-state decode: two-point over the device-resident loop,
    # min over REPS fresh-prefilled repetitions (the blocking sync that
    # ends each run jitters by several ms; min-of-reps keeps the 48-step
    # divisor from amplifying it). Context distribution is identical
    # across reps because each rep re-prefills fresh sequences.
    REPS = 3
    t_warms, t_gens, t_put_warms, t_put_gens = [], [], [], []
    wall_gen = None
    for rep in range(REPS):
        ruids = [1000 + 100 * rep + i for i in range(clients)]
        first = engine.put(ruids, prompts)
        start = [int(np.argmax(first[u])) for u in ruids]
        t0 = time.perf_counter()
        toks_w = engine.decode_loop(ruids, start, warm_tokens)
        t_warms.append(time.perf_counter() - t0)
        t0 = time.perf_counter()
        toks = engine.decode_loop(ruids, [int(toks_w[i, -1]) for i in
                                          range(clients)], gen_tokens)
        t_gens.append(time.perf_counter() - t0)
        wall_gen = t_gens[-1]
        assert toks.shape == (clients, gen_tokens)

        # put()-path decode: host scheduling every step, device token
        # feedback (decode_step greedy), two-point the same way
        last = [int(toks[i, -1]) for i in range(clients)]

        def put_chain(first_tokens, steps):
            t0 = time.perf_counter()
            _, nxt = engine.decode_step(ruids, first_tokens, greedy=True)
            for _ in range(steps - 1):
                _, nxt = engine.decode_step(ruids, nxt, greedy=True)
            jax.block_until_ready(nxt)
            return time.perf_counter() - t0, nxt

        t_pw, mid = put_chain(last, warm_tokens)
        t_put_warms.append(t_pw)
        t_pg, _ = put_chain(mid, gen_tokens)
        t_put_gens.append(t_pg)
        engine.flush(ruids)

    spread = gen_tokens - warm_tokens
    step_s = (min(t_gens) - min(t_warms)) / spread
    tok_s = clients / step_s
    wall_tok_s = clients * gen_tokens / wall_gen
    put_step_s = (min(t_put_gens) - min(t_put_warms)) / spread

    p50_ttft = float(np.percentile(ttft_ms, 50))
    p95_ttft = float(np.percentile(ttft_ms, 95))

    # memory roofline for batched decode on this chip
    n_params = sum(int(np.prod(l.shape))
                   for l in jax.tree_util.tree_leaves(params))
    model_bytes = n_params * 2  # bf16 serving weights
    hbm_bw = hbm_bandwidth_bytes_per_s()
    roofline_tok_s = clients * hbm_bw / model_bytes
    vs = tok_s / (0.5 * roofline_tok_s)

    print(json.dumps({
        "metric": "fastgen_decode_tokens_per_sec_125m",
        "value": round(tok_s, 1),
        "unit": "tokens/s/chip",
        "vs_baseline": round(vs, 4),
        "extra": {
            "p50_ttft_ms": round(p50_ttft, 2),
            "p95_ttft_ms": round(p95_ttft, 2),
            "clients": clients,
            "prompt_len": prompt_len,
            "gen_tokens": gen_tokens,
            "decode_step_ms": round(1000 * step_s, 3),
            "decode_wall_step_ms": round(1000 * wall_gen / gen_tokens, 3),
            "wall_tokens_per_sec": round(wall_tok_s, 1),
            "put_decode_step_ms": round(1000 * put_step_s, 3),
            "roofline_tok_s": round(roofline_tok_s, 1),
            "params_m": round(n_params / 1e6, 1),
            "kv_heads": cfg.num_key_value_heads,
            "dtype": "bfloat16",
            "platform": jax.devices()[0].platform,
        },
    }))


def _random_int8_llama_params(cfg, groups: int = 16):
    """Random-init Llama params with every matmul weight an int8
    {'q','scale'} record, built DIRECTLY on device — the bf16 tree never
    exists, so a 7B fits comfortably (reference FastGen loads Llama-2-7B
    fp16 into 4xA100; the single-v5e equivalent is int8-resident weights,
    blogs/deepspeed-fastgen/README.md:139-168).  Scales target the usual
    1/sqrt(fan_in) weight magnitude so logits stay finite."""
    import jax
    import jax.numpy as jnp

    H, I, V, L = (cfg.hidden_size, cfg.intermediate_size, cfg.vocab_size,
                  cfg.num_hidden_layers)
    kv = cfg.num_key_value_heads * cfg.head_dim
    keys = iter(jax.random.split(jax.random.key(0), 8 * L + 4))

    def rec(shape):
        k_dim = shape[0]
        q = jax.random.randint(next(keys), shape, -127, 128, jnp.int8)
        # int8 uniform(-127,127) std ~73.3; scale for weight std 1/sqrt(K)
        scale = jnp.full((groups,), 1.0 / (73.3 * k_dim ** 0.5),
                         jnp.float32)
        return {"q": q, "scale": scale}

    def layer():
        return {
            "self_attn": {"q_proj": {"kernel": rec((H, H))},
                          "k_proj": {"kernel": rec((H, kv))},
                          "v_proj": {"kernel": rec((H, kv))},
                          "o_proj": {"kernel": rec((H, H))}},
            "mlp": {"gate_proj": {"kernel": rec((H, I))},
                    "up_proj": {"kernel": rec((H, I))},
                    "down_proj": {"kernel": rec((I, H))}},
            "input_layernorm": {"scale": jnp.ones((H,), jnp.float32)},
            "post_attention_layernorm": {"scale": jnp.ones((H,),
                                                           jnp.float32)},
        }

    emb = (jax.random.normal(next(keys), (V, H), jnp.bfloat16) * 0.02)
    model = {"embed_tokens": {"embedding": emb},
             "norm": {"scale": jnp.ones((H,), jnp.float32)}}
    for i in range(L):
        model[f"layers_{i}"] = layer()
    return {"model": model, "lm_head": {"kernel": rec((H, V))}}


def measure_7b(clients: int = 8, prompt_len: int = 256,
               warm_tokens: int = 16, gen_tokens: int = 48,
               block_size: int = 128):
    """Serve Llama-2-7B geometry int8-resident on ONE chip through
    InferenceEngineV2; returns the result dict (also embedded in
    bench.py's driver-captured JSON).

    Decode headline is the WALL-CLOCK rate of the device-resident
    ``decode_loop`` (one dispatch runs the whole scan on-chip, so wall
    time is device time plus a single host sync); the
    marginal two-point rate is reported alongside.  The roofline
    denominator counts the int8 weight bytes each batched step streams
    PLUS the KV-pool read the attention performs (VERDICT r4 weak #3:
    a weights-only roofline ignores the KV term that grows with
    context)."""
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.inference.v2 import (InferenceEngineV2,
                                            RaggedInferenceEngineConfig)
    from deepspeed_tpu.inference.v2.model_implementations import RaggedLlama
    from deepspeed_tpu.models import LlamaConfig

    cfg = LlamaConfig.llama2_7b(dtype=jnp.bfloat16)   # 4096/11008/32L/32H
    params = _random_int8_llama_params(cfg)

    max_ctx = prompt_len + 1 + warm_tokens + gen_tokens + 8
    eng_cfg = RaggedInferenceEngineConfig.from_dict({
        "state_manager": {"max_ragged_batch_size": 512,
                          "max_ragged_sequence_count": clients,
                          "max_context": max_ctx},
        "kv_cache": {"block_size": block_size},
    })
    engine = InferenceEngineV2(RaggedLlama(cfg, block_size), params,
                               eng_cfg)

    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, cfg.vocab_size, size=(prompt_len,)).tolist()
               for _ in range(clients)]
    uids = list(range(clients))

    # warmup/compile: the prefill bucket and the ONE decode scan chunk
    # (warm=16, gen=48=3x16) so exactly one 32-layer scan is compiled
    wuids = [100 + i for i in range(clients)]
    first = engine.put(wuids, prompts)
    start = [int(np.argmax(first[u])) for u in wuids]
    engine.decode_loop(wuids, start, warm_tokens)
    engine.flush(wuids)
    # the TTFT loop submits ONE client at a time — warm that prefill
    # bucket too or the first client pays its compile
    engine.put([300], [prompts[0]])
    engine.flush([300])

    ttft_ms = []
    for uid in uids:
        t0 = time.perf_counter()
        logits = engine.put([uid], [prompts[uid]])
        int(np.argmax(logits[uid]))
        ttft_ms.append((time.perf_counter() - t0) * 1000)
    engine.flush(uids)

    REPS = 2
    t_warms, t_gens = [], []
    for rep in range(REPS):
        ruids = [1000 + 100 * rep + i for i in range(clients)]
        first = engine.put(ruids, prompts)
        start = [int(np.argmax(first[u])) for u in ruids]
        t0 = time.perf_counter()
        toks_w = engine.decode_loop(ruids, start, warm_tokens)
        t_warms.append(time.perf_counter() - t0)
        t0 = time.perf_counter()
        toks = engine.decode_loop(
            ruids, [int(toks_w[i, -1]) for i in range(clients)], gen_tokens)
        t_gens.append(time.perf_counter() - t0)
        assert toks.shape == (clients, gen_tokens)
        engine.flush(ruids)

    wall_step_s = min(t_gens) / gen_tokens
    wall_tok_s = clients / wall_step_s
    marg_step_s = (min(t_gens) - min(t_warms)) / (gen_tokens - warm_tokens)
    marg_tok_s = clients / marg_step_s

    # roofline: int8 weight bytes streamed per batched step + KV read
    def _rec_bytes(t):
        return sum(l.size * l.dtype.itemsize
                   for l in jax.tree_util.tree_leaves(t))

    weight_bytes = _rec_bytes(params) - \
        params["model"]["embed_tokens"]["embedding"].size * 2  # gather-only
    sm = engine.state_manager
    # KV-read term: decode routes through the O(live-context) paged
    # kernel (head_dim 128), which reads each sequence's live context —
    # use the mean context over the measured gen window, NOT the whole
    # pool (that would overstate the denominator and flatter
    # vs_roofline)
    mean_ctx = prompt_len + 1 + warm_tokens + gen_tokens / 2
    kv_bytes = int(clients * mean_ctx * sm.kv_cache.per_token_bytes)
    bw = hbm_bandwidth_bytes_per_s()
    roofline_tok_s = clients * bw / (weight_bytes + kv_bytes)

    return {
        "metric": "fastgen_7b_int8_decode_tokens_per_sec",
        "value": round(wall_tok_s, 1),
        "unit": "tokens/s/chip",
        "vs_roofline": round(wall_tok_s / (0.5 * roofline_tok_s), 4),
        "p50_ttft_ms": round(float(np.percentile(ttft_ms, 50)), 2),
        "p95_ttft_ms": round(float(np.percentile(ttft_ms, 95)), 2),
        "decode_wall_step_ms": round(1000 * wall_step_s, 3),
        "decode_marginal_step_ms": round(1000 * marg_step_s, 3),
        "marginal_tokens_per_sec": round(marg_tok_s, 1),
        "clients": clients, "prompt_len": prompt_len,
        "gen_tokens": gen_tokens,
        "geometry": "llama2-7b (4096h/11008i/32L/32H) int8 weights",
        "weight_gb": round(weight_bytes / 1e9, 2),
        "kv_read_gb_per_step": round(kv_bytes / 1e9, 2),
        "roofline_tok_s": round(roofline_tok_s, 1),
    }


def _tracer_overhead(engine, prompts, sampling, clients: int,
                     trace_out=None) -> dict:
    """A/B the decode-tick cost of host-side tracing: the same decode-
    dominated workload through an untraced scheduler, then a traced one
    (ring-buffer spans for every tick/phase/request transition), over
    the SAME warm engine.  Median-of-ticks keeps one scheduler's noise
    spike from deciding the verdict.  With ``trace_out`` the traced
    arm's timeline is written as Chrome/Perfetto trace-event JSON."""
    from deepspeed_tpu.observability import Tracer, write_chrome_trace
    from deepspeed_tpu.serving import ContinuousBatchScheduler

    def arm(tracer):
        sched = ContinuousBatchScheduler(engine, tracer=tracer)
        for i in range(clients):
            sched.submit(prompts[i], sampling=sampling)
        sched.run_until_idle()
        return list(sched.metrics.decode_tick_s)

    # interleaved U/T/U/T arms: host noise (CPU contention, thermal
    # drift) hits both modes alike instead of whichever ran first
    tracer = Tracer(capacity=65536, tid="bench")
    untraced_ticks, traced_ticks = [], []
    for _round in range(2):
        untraced_ticks.extend(arm(None))
        traced_ticks.extend(arm(tracer))
    untraced_s = float(np.median(np.asarray(untraced_ticks, np.float64)))
    traced_s = float(np.median(np.asarray(traced_ticks, np.float64)))
    events = tracer.export_events()
    out = {
        "decode_tick_ms_untraced": round(untraced_s * 1e3, 4),
        "decode_tick_ms_traced": round(traced_s * 1e3, 4),
        "tracer_overhead_pct": round(
            (traced_s / max(untraced_s, 1e-12) - 1.0) * 100.0, 3),
        "trace_events": len(events),
    }
    if trace_out:
        write_chrome_trace(trace_out, events)
        out["trace_path"] = trace_out
    return out


def measure_scheduler(n_requests: int = 32, rate_rps: float = 16.0,
                      prompt_len: int = 192, gen_tokens: int = 48,
                      clients: int = 8, block_size: int = 128,
                      kv_fraction: float = 0.7, seed: int = 0,
                      trace_out=None):
    """Scheduler-mode serving benchmark: Poisson arrivals driven through
    the ``deepspeed_tpu.serving`` continuous-batching scheduler (Dynamic
    SplitFuse packing + KV-pressure preemption), instead of the
    hand-driven fixed client set above.

    The KV pool is sized to ``kv_fraction`` of the worst-case concurrent
    demand, so bursts genuinely exercise the preempt/resume path; the
    preemption rate is part of the report.  Goodput counts only finished
    requests' tokens — recompute work thrown away by preemption is the
    system's cost, not its output.

    Returns the result dict (printed as the one-line JSON by ``main``).
    """
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.inference.v2 import (InferenceEngineV2,
                                            RaggedInferenceEngineConfig)
    from deepspeed_tpu.inference.v2.model_implementations import RaggedLlama
    from deepspeed_tpu.models import LlamaConfig, LlamaForCausalLM
    from deepspeed_tpu.serving import (ContinuousBatchScheduler,
                                       SamplingParams)

    cfg = LlamaConfig(vocab_size=32000, hidden_size=768,
                      intermediate_size=2048, num_hidden_layers=12,
                      num_attention_heads=6, num_key_value_heads=2,
                      max_position_embeddings=2048, dtype=jnp.bfloat16)
    params = LlamaForCausalLM(cfg).init(
        jax.random.key(0), np.zeros((1, 8), np.int32))["params"]
    params = jax.tree.map(lambda p: p.astype(jnp.bfloat16), params)

    max_ctx = prompt_len + gen_tokens + 8
    per_seq_blocks = -(-max_ctx // block_size)
    worst = clients * per_seq_blocks
    num_blocks = max(int(worst * kv_fraction), 2 * per_seq_blocks) + 1
    eng_cfg = RaggedInferenceEngineConfig.from_dict({
        "state_manager": {"max_ragged_batch_size": 512,
                          "max_ragged_sequence_count": clients,
                          "max_context": max_ctx},
        "kv_cache": {"block_size": block_size, "num_blocks": num_blocks},
    })
    engine = InferenceEngineV2(RaggedLlama(cfg, block_size), params, eng_cfg)

    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, cfg.vocab_size, size=(prompt_len,)).tolist()
               for _ in range(n_requests)]
    sampling = SamplingParams(greedy=True, max_new_tokens=gen_tokens)

    # warmup: replay a small burst of the SAME workload (same prompt
    # length / generation length / concurrency) through a throwaway
    # scheduler, so every program the measured loop packs — the
    # two-segment put programs (single-token rows + whole tiles) of each
    # row count, or the token buckets of an engine whose budget is no
    # whole number of tiles — is compiled before the clock starts
    # (programs are cached on the shared engine)
    warm = ContinuousBatchScheduler(engine)
    n_warm = min(clients, n_requests)
    warm.run_with_arrivals(prompts[:n_warm], [0.0] * n_warm,
                           sampling=sampling)
    warm.run_with_arrivals([prompts[0]], [0.0], sampling=sampling)

    sched = ContinuousBatchScheduler(engine)
    arrivals = np.cumsum(rng.exponential(1.0 / rate_rps, size=n_requests))
    t0 = time.perf_counter()
    sched.run_with_arrivals(prompts, arrivals, sampling=sampling)
    wall = time.perf_counter() - t0

    snap = sched.metrics.snapshot()
    finished = [r for r in sched.finished_requests
                if r.state.value == "finished"]
    assert len(finished) == n_requests, \
        f"{len(finished)}/{n_requests} finished ({snap})"
    goodput = snap["total_tokens"] / wall

    # tracer-overhead A/B over the same warm engine (ISSUE 12: tracing
    # must stay <2% of decode-tick wall; PERFLOG records the number)
    overhead = _tracer_overhead(engine, prompts, sampling, clients,
                                trace_out=trace_out)

    # roofline context: batched decode at full concurrency streams the
    # weights once per step (same denominator as the steady-state bench)
    n_params = sum(int(np.prod(l.shape))
                   for l in jax.tree_util.tree_leaves(params))
    roofline_tok_s = clients * hbm_bandwidth_bytes_per_s() / (n_params * 2)

    # compile-time HLO memory ledger for the decode program (abstract
    # re-lowering — the live cache is never touched), so the BENCH JSON
    # carries the memory evidence perf_report renders
    from deepspeed_tpu.observability.memory import unavailable_entry
    try:
        mem_ledger = engine.capture_memory_ledger().to_json()
    except Exception as e:  # noqa: BLE001 — absence is a record
        mem_ledger = {"schema": "ds-memory-ledger-v1", "entries": {
            "decode_step": unavailable_entry(
                f"{type(e).__name__}: {e}")}}

    return {
        "metric": "serving_scheduler_goodput_tokens_per_sec",
        "value": round(goodput, 1),
        "unit": "tokens/s/chip",
        "vs_baseline": round(goodput / (0.5 * roofline_tok_s), 4),
        "extra": {
            "p50_ttft_ms": round(1000 * snap.get("p50_ttft_s", 0.0), 2),
            "p95_ttft_ms": round(1000 * snap.get("p95_ttft_s", 0.0), 2),
            "p50_tpot_ms": round(1000 * snap.get("p50_tpot_s", 0.0), 3),
            "p95_tpot_ms": round(1000 * snap.get("p95_tpot_s", 0.0), 3),
            "p50_queue_wait_ms": round(
                1000 * snap.get("p50_queue_wait_s", 0.0), 2),
            "preemptions": int(snap["preemptions"]),
            "preemption_rate": round(snap["preemption_rate"], 4),
            "n_requests": n_requests,
            "rate_rps": rate_rps,
            "prompt_len": prompt_len,
            "gen_tokens": gen_tokens,
            "max_concurrency": clients,
            "kv_blocks": num_blocks,
            "kv_fraction_of_worst_case": kv_fraction,
            "wall_s": round(wall, 2),
            "platform": jax.devices()[0].platform,
            # geometry + memory evidence: perf_report's decode waterfall
            # and memory-ledger table read straight from this record
            "geometry": {"hidden": cfg.hidden_size,
                         "layers": cfg.num_hidden_layers,
                         "heads": cfg.num_attention_heads,
                         "kv_heads": cfg.num_key_value_heads,
                         "intermediate": cfg.intermediate_size,
                         "vocab": cfg.vocab_size,
                         "dtype": "bfloat16",
                         "kv_dtype": "bfloat16"},
            "memory_ledger": mem_ledger,
            **overhead,
        },
    }


def _spec_extra(schedulers, draft_k: int) -> dict:
    """Aggregate speculative COUNTERS across schedulers and derive the
    reportable rates once (summing per-scheduler rates is meaningless)."""
    tot = {"ticks": 0, "drafted": 0, "accepted": 0, "emitted": 0}
    for sched in schedulers:
        st = sched.spec_stats
        for k in tot:
            tot[k] += int(getattr(st, k))
    return {
        "speculative": True,
        "draft_k": draft_k,
        "accept_rate": round(tot["accepted"] / max(tot["drafted"], 1), 4),
        "tokens_per_weight_pass": round(
            tot["emitted"] / max(tot["ticks"], 1), 3),
        "spec_ticks": tot["ticks"],
    }


def measure_speculative(draft_k: int = 4, n_requests: int = 12,
                        rate_rps: float = 16.0, prompt_len: int = 192,
                        gen_tokens: int = 48, clients: int = 8,
                        block_size: int = 128, seed: int = 0):
    """Speculative-decoding serving benchmark: the scheduler-mode Poisson
    workload run twice over the 125M GQA geometry — a non-speculative
    baseline, then with the n-gram self-drafter + K-draft multi-token
    verify — asserting greedy output is BIT-IDENTICAL between the two
    and reporting accept-rate, tokens-per-weight-pass, and effective
    tok/s A/B.

    Prompts carry a repeated phrase (the retrieval/summarisation shape
    prompt-lookup drafting exists for) so the drafter has material; the
    accept-rate reported is measured, not assumed.

    Runs in f32: the bit-parity assertion is the whole point of the
    A/B, and bitwise logits equality across the decode and verify
    programs is the f32 contract (same contract preempt/recompute
    resume relies on).  bf16 rounds near-ties differently across
    program shapes — the exact caveat ``measure_shared_prefix``
    documents for warm-vs-cold bucket programs — so a bf16 parity
    assert would flake on ties, not on real divergence.
    """
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.inference.v2 import (InferenceEngineV2,
                                            RaggedInferenceEngineConfig)
    from deepspeed_tpu.inference.v2.model_implementations import RaggedLlama
    from deepspeed_tpu.models import LlamaConfig, LlamaForCausalLM
    from deepspeed_tpu.serving import (ContinuousBatchScheduler,
                                       SamplingParams, SpeculativeConfig)

    cfg = LlamaConfig(vocab_size=32000, hidden_size=768,
                      intermediate_size=2048, num_hidden_layers=12,
                      num_attention_heads=6, num_key_value_heads=2,
                      max_position_embeddings=2048, dtype=jnp.float32)
    params = LlamaForCausalLM(cfg).init(
        jax.random.key(0), np.zeros((1, 8), np.int32))["params"]

    # K lookahead slots of context headroom: without them the last
    # gen_tokens' verify passes fail can_schedule and silently fall
    # back to plain decode, skewing accept-rate low at exactly the
    # large K values --draft-k exists to sweep
    max_ctx = prompt_len + gen_tokens + draft_k + 1 + 8
    per_seq_blocks = -(-max_ctx // block_size)
    num_blocks = clients * per_seq_blocks + 1

    def make_engine():
        eng_cfg = RaggedInferenceEngineConfig.from_dict({
            "state_manager": {"max_ragged_batch_size": 512,
                              "max_ragged_sequence_count": clients,
                              "max_context": max_ctx},
            "kv_cache": {"block_size": block_size,
                         "num_blocks": num_blocks},
        })
        return InferenceEngineV2(RaggedLlama(cfg, block_size), params,
                                 eng_cfg)

    rng = np.random.default_rng(seed)
    phrase_len = 24
    prompts = []
    for _ in range(n_requests):
        phrase = rng.integers(0, cfg.vocab_size,
                              size=(phrase_len,)).tolist()
        reps = prompt_len // phrase_len
        tail = rng.integers(0, cfg.vocab_size,
                            size=(prompt_len - reps * phrase_len,)).tolist()
        prompts.append(phrase * reps + tail)
    sampling = SamplingParams(greedy=True, max_new_tokens=gen_tokens)
    arrivals = np.cumsum(rng.exponential(1.0 / rate_rps, size=n_requests))

    def run(spec):
        # warm and measure over the SAME engine (jit programs cache on
        # the engine) — the speculative arm compiles strictly more
        # programs than the baseline, so compiling inside the measured
        # window would deflate vs_baseline by compile time
        eng = make_engine()
        warm = ContinuousBatchScheduler(eng, speculative=spec)
        n_warm = min(clients, n_requests)
        warm.run_with_arrivals(prompts[:n_warm], [0.0] * n_warm,
                               sampling=sampling)
        sched = ContinuousBatchScheduler(eng, speculative=spec)
        t0 = time.perf_counter()
        reqs = sched.run_with_arrivals(prompts, arrivals,
                                       sampling=sampling)
        wall = time.perf_counter() - t0
        bad = [r for r in reqs if r.state.value != "finished"]
        assert not bad, [(r.uid, r.state.value, r.finish_reason)
                         for r in bad]
        return sched, [r.generated for r in reqs], wall

    base_sched, base_out, base_wall = run(None)
    spec_cfg = SpeculativeConfig(draft_k=draft_k)
    spec_sched, spec_out, spec_wall = run(spec_cfg)
    # the acceptance rule reuses the (seed, uid, position)-keyed sampler:
    # greedy output must be bit-identical at every K
    assert spec_out == base_out, \
        "speculative greedy output diverged from the baseline"

    st = spec_sched.spec_stats
    base_snap = base_sched.metrics.snapshot()
    spec_snap = spec_sched.metrics.snapshot()
    total_tokens = sum(len(o) for o in spec_out)
    eff_tok_s = total_tokens / spec_wall
    base_tok_s = total_tokens / base_wall

    return {
        "metric": "serving_speculative_decode_tokens_per_sec",
        "value": round(eff_tok_s, 1),
        "unit": "tokens/s/chip",
        "vs_baseline": round(eff_tok_s / max(base_tok_s, 1e-9), 4),
        "extra": {
            "draft_k": draft_k,
            "dtype": "float32",
            "greedy_bit_identical": True,
            "accept_rate": round(st.accept_rate, 4),
            "tokens_per_weight_pass": round(st.tokens_per_pass, 3),
            "tokens_per_request_tick": round(
                spec_snap.get("tokens_per_request_tick", 1.0), 3),
            "spec_ticks": int(st.ticks),
            "fallback_ticks": int(st.fallback_ticks),
            "drafted": int(st.drafted),
            "accepted": int(st.accepted),
            "baseline_tok_s": round(base_tok_s, 1),
            "effective_tok_s": round(eff_tok_s, 1),
            "tpot_delivered_ms": round(
                1000 * spec_snap.get("tpot_delivered_s", 0.0), 3),
            "baseline_tpot_delivered_ms": round(
                1000 * base_snap.get("tpot_delivered_s", 0.0), 3),
            "n_requests": n_requests,
            "prompt_len": prompt_len,
            "gen_tokens": gen_tokens,
            "max_concurrency": clients,
            "wall_s": round(spec_wall, 2),
            "baseline_wall_s": round(base_wall, 2),
            "platform": jax.devices()[0].platform,
        },
    }


def measure_shared_prefix(n_requests: int = 64, tenants: int = 4,
                          shared_prefix_ratio: float = 0.9,
                          prompt_len: int = 256, gen_tokens: int = 16,
                          clients: int = 8, block_size: int = 32,
                          replicas: int = 2, seed: int = 0,
                          speculative: bool = False, draft_k: int = 4):
    """Shared-prefix serving workload: per-tenant prompt pools behind the
    cache-aware router, measuring what the radix prefix cache buys.

    Each tenant owns a fixed ``shared_prefix_ratio * prompt_len``-token
    system prompt; every request appends a unique tail.  Phase 1 measures
    TTFT with the cache COLD (first request per tenant) then WARM
    (subsequent requests one at a time, so TTFT isolates prefill cost).
    Phase 2 drives the remaining requests through ``replicas``
    cache-aware-routed schedulers and reports the aggregate cache-hit
    rate and prefill tokens saved.
    """
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.inference.v2 import (InferenceEngineV2,
                                            RaggedInferenceEngineConfig)
    from deepspeed_tpu.inference.v2.model_implementations import RaggedLlama
    from deepspeed_tpu.models import LlamaConfig, LlamaForCausalLM
    from deepspeed_tpu.serving import (CacheAwareRouter,
                                       ContinuousBatchScheduler,
                                       SamplingParams, SpeculativeConfig,
                                       make_self_drafter)

    cfg = LlamaConfig(vocab_size=32000, hidden_size=768,
                      intermediate_size=2048, num_hidden_layers=12,
                      num_attention_heads=6, num_key_value_heads=2,
                      max_position_embeddings=2048, dtype=jnp.bfloat16)
    params = LlamaForCausalLM(cfg).init(
        jax.random.key(0), np.zeros((1, 8), np.int32))["params"]
    params = jax.tree.map(lambda p: p.astype(jnp.bfloat16), params)

    max_ctx = prompt_len + gen_tokens + (draft_k + 1 if speculative
                                         else 0) + 8
    per_seq = -(-max_ctx // block_size)
    prefix_blocks = -(-prompt_len // block_size)
    # room for all live sequences plus every tenant's warm prefix
    num_blocks = clients * per_seq + tenants * prefix_blocks + 1
    eng_cfg = RaggedInferenceEngineConfig.from_dict({
        "state_manager": {"max_ragged_batch_size": 512,
                          "max_ragged_sequence_count": clients,
                          "max_context": max_ctx},
        "kv_cache": {"block_size": block_size, "num_blocks": num_blocks,
                     "enable_prefix_cache": True},
    })

    def make_sched():
        eng = InferenceEngineV2(RaggedLlama(cfg, block_size), params,
                                eng_cfg)
        spec = SpeculativeConfig(
            draft_k=draft_k,
            drafter=make_self_drafter(eng)) if speculative else None
        return ContinuousBatchScheduler(eng, speculative=spec)

    rng = np.random.default_rng(seed)
    shared_len = int(shared_prefix_ratio * prompt_len)
    pools = {f"t{i}": rng.integers(0, cfg.vocab_size,
                                   size=(shared_len,)).tolist()
             for i in range(tenants)}

    def make_prompt(tenant):
        tail = rng.integers(0, cfg.vocab_size,
                            size=(prompt_len - shared_len,)).tolist()
        return pools[tenant] + tail

    sampling = SamplingParams(greedy=True, max_new_tokens=gen_tokens)
    router = CacheAwareRouter([make_sched() for _ in range(replicas)])

    # warmup compile: a throwaway tenant's worth of work on each replica,
    # plus a tail-sized prompt so the warm path's small prefill bucket is
    # compiled before the clock starts
    for rep in router.replicas:
        w = rep.scheduler.submit(
            rng.integers(0, cfg.vocab_size, size=(prompt_len,)).tolist(),
            sampling=sampling)
        rep.scheduler.run_until_idle()
        assert w.state.value == "finished"
        rep.scheduler.submit(
            rng.integers(0, cfg.vocab_size,
                         size=(prompt_len - shared_len + block_size,)
                         ).tolist(),
            sampling=sampling)
        rep.scheduler.run_until_idle()
        w2 = rep.scheduler.submit(w.prompt, sampling=sampling)  # warm path
        rep.scheduler.run_until_idle()
        # token-exactness of warm runs is asserted by the f32 unit tests;
        # here (bf16) a near-tie can argmax differently between the
        # prefill-bucket and warm-bucket programs, so only completion is
        # checked
        assert w2.state.value == "finished", w2.finish_reason
        # warmup traffic must not pollute the measured hit accounting
        pc = rep.scheduler.engine.state_manager.prefix_cache
        pc.stats = type(pc.stats)()

    # --- phase 1: cold vs warm TTFT, one request at a time
    cold_ttft_ms, warm_ttft_ms = [], []
    used = 0
    for i, tenant in enumerate(pools):
        for j in range(3):
            req = router.submit(make_prompt(tenant), tenant=tenant,
                                sampling=sampling)
            router.run_until_idle()
            used += 1
            (cold_ttft_ms if j == 0 else warm_ttft_ms).append(
                1000 * req.ttft)

    # --- phase 2: concurrent Poisson-ish mix over the fleet
    total_prompt_tokens = 0
    reqs = []
    for i in range(max(n_requests - used, 0)):
        tenant = f"t{i % tenants}"
        prompt = make_prompt(tenant)
        total_prompt_tokens += len(prompt)
        reqs.append(router.submit(prompt, tenant=tenant, sampling=sampling))
        router.step()
    t0 = time.perf_counter()
    router.run_until_idle()
    wall = time.perf_counter() - t0

    bad = [r for r in reqs if r.state.value != "finished"]
    assert not bad, [(r.uid, r.state.value, r.finish_reason) for r in bad]

    # aggregate prefix-cache accounting across replicas
    agg = {}
    for rep in router.replicas:
        for k, v in rep.scheduler.engine.state_manager.prefix_cache \
                .stats.as_dict().items():
            agg[k] = agg.get(k, 0.0) + v
    # denominator = tokens actually issued: phase 1 always runs 3 prompts
    # per tenant, so the total can exceed n_requests when it is small
    all_prompt_tokens = used * prompt_len + total_prompt_tokens
    saved_pct = 100.0 * agg["hit_tokens"] / max(all_prompt_tokens, 1)
    p50 = lambda v: float(np.percentile(v, 50))  # noqa: E731

    spec_extra = _spec_extra(
        [rep.scheduler for rep in router.replicas],
        draft_k) if speculative else {}

    cold, warm = p50(cold_ttft_ms), p50(warm_ttft_ms)
    return {
        "metric": "serving_shared_prefix_cache",
        "value": round(saved_pct, 2),
        "unit": "% prefill tokens saved",
        "vs_baseline": round(saved_pct / 100.0, 4),
        "extra": {
            **spec_extra,
            "shared_prefix_ratio": shared_prefix_ratio,
            "tenants": tenants,
            "n_requests": n_requests,
            "n_requests_issued": used + len(reqs),
            "prompt_len": prompt_len,
            "block_size": block_size,
            "replicas": replicas,
            "cache_hit_rate": round(agg["hits"] / max(agg["lookups"], 1), 4),
            "prefill_tokens_saved": int(agg["hit_tokens"]),
            "prefill_tokens_saved_pct": round(saved_pct, 2),
            "cold_ttft_ms_p50": round(cold, 2),
            "warm_ttft_ms_p50": round(warm, 2),
            "warm_ttft_speedup": round(cold / max(warm, 1e-9), 2),
            "router_cache_hit_routed": int(
                router.snapshot()["cache_hit_routed"]),
            "routed_per_replica": {
                rep.name: router.routed[rep.name]
                for rep in router.replicas},
            "evictions": int(agg["evicted_blocks"]),
            "cow_forks": int(agg["cow_forks"]),
            "phase2_wall_s": round(wall, 2),
            "platform": jax.devices()[0].platform,
        },
    }


def measure_session_mix(idle_fraction: float = 0.5,
                        resume_cadence: int = 3,
                        max_sessions: int = 36,
                        prompt_len: int = 88, turn_tail: int = 16,
                        turn_gen: int = 8, block_size: int = 16,
                        budget_blocks_bf16: int = 56,
                        chatty_window: int = 2, max_turns: int = 4,
                        shared_prefix: bool = False,
                        shared_prefix_ratio: float = 0.5,
                        tenants: int = 2,
                        fleet: int | None = None, seed: int = 0):
    """Chatty-vs-idle session-mix capacity benchmark — the evidence
    harness for the KV-quantization + host-tier capacity claim.

    Sessions are admitted one at a time; a ``1 - idle_fraction``
    fraction are *chatty* (they take another turn every round while
    recently admitted) and the rest are *idle* (probed — resumed with
    their full history — every ``resume_cadence`` rounds, oldest-idle
    first, the LRU worst case).  A session is **resident** while every
    one of its resumes is served entirely from warm/restorable KV — no
    recompute prefill and no scheduler preemption anywhere.

    Two arms over the SAME HBM byte budget (``budget_blocks_bf16``
    bf16-blocks' worth):

    * baseline — bf16 KV, no host tier: LRU eviction *destroys* cold
      blocks, so a resume past HBM capacity silently recomputes;
    * treatment — int8 KV (per-row/per-head scales, ~1.9x blocks for
      the same bytes) + host cold tier: cold blocks spool to host RAM
      and restore bit-exact on resume.

    ``max_resident_sessions`` per arm = sessions admitted when the first
    recompute/preemption happened (the treatment arm typically runs to
    the ``max_sessions`` cap — capacity is then host-RAM-bounded, and
    the reported ratio is a floor).  Composes with ``--shared-prefix``
    (per-tenant system prompts prepended to every session) and
    ``--fleet N`` (both arms run N replicas behind the fleet router;
    warm-prefix affinity routes resumes home).
    """
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.inference.v2 import (InferenceEngineV2,
                                            RaggedInferenceEngineConfig)
    from deepspeed_tpu.inference.v2.model_implementations import RaggedLlama
    from deepspeed_tpu.models import LlamaConfig, LlamaForCausalLM
    from deepspeed_tpu.serving import (ContinuousBatchScheduler,
                                       SamplingParams)

    # small geometry: this is a CAPACITY bench (blocks, bytes, spool/
    # restore traffic), not a throughput roofline — tokens/s is
    # reported as context, not as the headline
    cfg = LlamaConfig(vocab_size=1024, hidden_size=128,
                      intermediate_size=256, num_hidden_layers=2,
                      num_attention_heads=4, num_key_value_heads=2,
                      max_position_embeddings=512, dtype=jnp.float32)
    params = LlamaForCausalLM(cfg).init(
        jax.random.key(0), np.zeros((1, 8), np.int32))["params"]

    max_ctx = prompt_len + max_turns * (turn_tail + turn_gen) + 16
    # per_token_bytes from the cache itself (one-block throwaway pools),
    # so the equal-HBM-byte budget tracks the real storage layout
    # instead of a hand-copied formula that drifts when the scale
    # record layout changes
    from deepspeed_tpu.inference.v2.ragged import BlockedKVCache
    per_tok = {dt: BlockedKVCache(cfg.num_hidden_layers, 1, block_size,
                                  cfg.num_key_value_heads, cfg.head_dim,
                                  dt).per_token_bytes
               for dt in ("bf16", "int8")}
    budget_bytes = budget_blocks_bf16 * block_size * per_tok["bf16"]

    rng = np.random.default_rng(seed)
    shared_len = int(shared_prefix_ratio * prompt_len) if shared_prefix \
        else 0
    pools = {f"t{i}": rng.integers(0, cfg.vocab_size,
                                   size=(shared_len,)).tolist()
             for i in range(tenants)} if shared_prefix else {}

    def session_prompt(sid: int):
        tail = rng.integers(0, cfg.vocab_size,
                            size=(prompt_len - shared_len,)).tolist()
        if shared_prefix:
            return pools[f"t{sid % tenants}"] + tail
        return tail

    def make_cfg(kv_dtype: str, host_tier: bool):
        num_blocks = budget_bytes // (block_size * per_tok[kv_dtype]) + 1
        return RaggedInferenceEngineConfig.from_dict({
            "state_manager": {"max_ragged_batch_size": 256,
                              "max_ragged_sequence_count": 4,
                              "max_context": max_ctx},
            "kv_cache": {"block_size": block_size,
                         "num_blocks": int(num_blocks),
                         "dtype": kv_dtype,
                         "enable_prefix_cache": True,
                         "host_tier": host_tier},
        }), int(num_blocks)

    sampling = SamplingParams(greedy=True, max_new_tokens=turn_gen)

    def run_arm(kv_dtype: str, host_tier: bool) -> dict:
        eng_cfg, num_blocks = make_cfg(kv_dtype, host_tier)

        def factory(_name: str = "r"):
            eng = InferenceEngineV2(RaggedLlama(cfg, block_size), params,
                                    eng_cfg)
            return ContinuousBatchScheduler(eng)

        if fleet:
            from deepspeed_tpu.fleet import ServingFleet

            fl = ServingFleet(factory, replicas=int(fleet))
            scheds = [rep.scheduler for _p, rep in fl.pool_members()]

            def turn(sid, prompt):
                fr = fl.submit(prompt, tenant=f"s{sid}", sampling=sampling)
                fl.run_until_idle(max_ticks=20000)
                assert fr.state == "finished", (fr.state, fr.finish_reason)
                return list(prompt) + list(fr.tokens)

            def preemptions():
                return int(fl.snapshot()["fleet/preemptions"])
        else:
            sched = factory()
            scheds = [sched]

            def turn(sid, prompt):
                req = sched.submit(prompt, sampling=sampling)
                sched.run_until_idle()
                assert req.state.value == "finished", req.finish_reason
                return list(req.prompt) + list(req.generated)

            def preemptions():
                return int(sched.metrics.snapshot()["preemptions"])

        def hit_tokens():
            return sum(s.engine.state_manager.prefix_cache.stats.hit_tokens
                       for s in scheds)

        def tier():
            return [s.engine.state_manager.host_tier for s in scheds
                    if s.engine.state_manager.host_tier is not None]

        # warm the compile caches with one throwaway session per replica
        for i in range(len(scheds)):
            turn(10_000 + i, session_prompt(10_000 + i))

        histories: dict = {}
        turns_done: dict = {}
        last_touch: dict = {}
        is_idle = {s: (s * 2654435761 % 100) < idle_fraction * 100
                   for s in range(max_sessions)}
        clean_through = 0
        tokens_out = 0
        recompute_tokens = 0
        stop_reason = "cap"
        t0 = time.perf_counter()

        def resume(sid, round_no) -> bool:
            """One follow-up turn; returns False on the first resume
            that needed recompute (capacity exceeded)."""
            nonlocal tokens_out, recompute_tokens
            prev = histories[sid]
            # full blocks of the previous history whose KV was written
            # (the final emitted token's never was): an ideally warm
            # resume re-attaches exactly these
            expected = ((len(prev) - 1) // block_size) * block_size
            tail = rng.integers(0, cfg.vocab_size,
                                size=(turn_tail,)).tolist()
            before = hit_tokens()
            hist = turn(sid, prev + tail)
            tokens_out += turn_gen
            got = hit_tokens() - before
            histories[sid] = hist
            turns_done[sid] += 1
            last_touch[sid] = round_no
            if got < expected:
                recompute_tokens += expected - got
                return False
            return True

        for s in range(max_sessions):
            histories[s] = turn(s, session_prompt(s))
            turns_done[s] = 1
            last_touch[s] = s
            tokens_out += turn_gen
            ok = True
            # chatty activity: recently admitted chatty sessions keep
            # talking every round
            for c in range(max(0, s - chatty_window + 1), s + 1):
                if ok and not is_idle[c] and turns_done[c] < max_turns:
                    ok = resume(c, s)
            # idle probe: every resume_cadence rounds the LRU-oldest
            # idle session comes back — the strictest (least recently
            # used) capacity witness
            if ok and (s + 1) % resume_cadence == 0:
                idle_live = [x for x in range(s + 1)
                             if is_idle[x] and turns_done[x] < max_turns]
                if idle_live:
                    oldest = min(idle_live, key=lambda x: last_touch[x])
                    ok = resume(oldest, s)
            if not ok:
                stop_reason = "recompute"
                break
            if preemptions() > 0:
                stop_reason = "preemption"
                break
            clean_through = s + 1
        wall = time.perf_counter() - t0

        tiers = tier()
        tier_stats = {}
        if tiers:
            agg = {}
            for t in tiers:
                for k, v in t.stats.as_dict().items():
                    if k.endswith("_blocks"):
                        agg[k] = agg.get(k, 0.0) + v
                    else:
                        agg[k] = max(agg.get(k, 0.0), v)
            tier_stats = {
                "spooled_blocks": int(agg["spooled_blocks"]),
                "restored_blocks": int(agg["restored_blocks"]),
                "tier_dropped_blocks": int(agg["dropped_blocks"]),
                "tier_bytes": int(sum(t.bytes for t in tiers)),
                "spool_p50_ms": round(1000 * agg["spool_p50_s"], 3),
                "spool_p95_ms": round(1000 * agg["spool_p95_s"], 3),
                "restore_p50_ms": round(1000 * agg["restore_p50_s"], 3),
                "restore_p95_ms": round(1000 * agg["restore_p95_s"], 3),
            }
        return {
            "kv_dtype": kv_dtype, "host_tier": host_tier,
            "kv_blocks": num_blocks,
            "max_resident_sessions": clean_through,
            "stop_reason": stop_reason,
            "recompute_tokens": int(recompute_tokens),
            "preemptions": preemptions(),
            "tokens_per_sec": round(tokens_out / max(wall, 1e-9), 1),
            "wall_s": round(wall, 2),
            **tier_stats,
        }

    base = run_arm("bf16", host_tier=False)
    treat = run_arm("int8", host_tier=True)
    ratio = treat["max_resident_sessions"] / max(
        base["max_resident_sessions"], 1)
    capped = treat["stop_reason"] == "cap"

    return {
        "metric": "serving_session_mix_resident_sessions",
        "value": treat["max_resident_sessions"],
        "unit": "resident sessions",
        "vs_baseline": round(ratio, 4),
        "extra": {
            "baseline": base,
            "treatment": treat,
            "capacity_ratio": round(ratio, 4),
            # treatment hitting the session cap means capacity is
            # host-RAM-bounded — the ratio is a floor, not a ceiling
            "treatment_capped": capped,
            "idle_fraction": idle_fraction,
            "resume_cadence": resume_cadence,
            "max_sessions": max_sessions,
            "prompt_len": prompt_len,
            "turn_tail": turn_tail,
            "turn_gen": turn_gen,
            "block_size": block_size,
            "hbm_budget_bytes": int(budget_bytes),
            "shared_prefix": bool(shared_prefix),
            "fleet": int(fleet) if fleet else 0,
            "geometry": {"hidden": cfg.hidden_size,
                         "layers": cfg.num_hidden_layers,
                         "heads": cfg.num_attention_heads,
                         "kv_heads": cfg.num_key_value_heads,
                         "intermediate": cfg.intermediate_size,
                         "vocab": cfg.vocab_size,
                         "dtype": "float32", "kv_dtype": "int8"},
            "platform": __import__("jax").devices()[0].platform,
        },
    }


def measure_fleet(n_replicas: int = 2, disaggregate: str | None = None,
                  shared_prefix: bool = False,
                  shared_prefix_ratio: float = 0.9,
                  n_requests: int = 32, rate_rps: float = 16.0,
                  prompt_len: int = 192, gen_tokens: int = 48,
                  clients: int = 8, block_size: int = 128,
                  tenants: int = 4, seed: int = 0,
                  speculative: bool = False, draft_k: int = 4):
    """Fleet-mode serving benchmark: the full ``deepspeed_tpu.fleet``
    stack — N replicas behind the cache-aware router — under the
    existing Poisson workload (or the ``--shared-prefix`` per-tenant
    workload), reporting fleet goodput, TTFT/TPOT percentiles, and (with
    ``--disaggregate P:D``) the prefill→decode KV-handoff latency.

    ``disaggregate="P:D"`` splits the fleet into P prefill and D decode
    replicas with KV moving between the pools; colocated mode runs
    ``n_replicas`` mixed replicas.  Every replica shares one params tree
    (weights are read-only) but owns its engine, KV pool, and scheduler.
    """
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.fleet import ServingFleet
    from deepspeed_tpu.inference.v2 import (InferenceEngineV2,
                                            RaggedInferenceEngineConfig)
    from deepspeed_tpu.inference.v2.model_implementations import RaggedLlama
    from deepspeed_tpu.models import LlamaConfig, LlamaForCausalLM
    from deepspeed_tpu.serving import (ContinuousBatchScheduler,
                                       SamplingParams, SpeculativeConfig,
                                       make_self_drafter)

    cfg = LlamaConfig(vocab_size=32000, hidden_size=768,
                      intermediate_size=2048, num_hidden_layers=12,
                      num_attention_heads=6, num_key_value_heads=2,
                      max_position_embeddings=2048, dtype=jnp.bfloat16)
    params = LlamaForCausalLM(cfg).init(
        jax.random.key(0), np.zeros((1, 8), np.int32))["params"]
    params = jax.tree.map(lambda p: p.astype(jnp.bfloat16), params)

    rng = np.random.default_rng(seed)
    shared_len = int(shared_prefix_ratio * prompt_len) if shared_prefix \
        else 0
    pools = {f"t{i}": rng.integers(0, cfg.vocab_size,
                                   size=(shared_len,)).tolist()
             for i in range(tenants)} if shared_prefix else {}

    def make_prompt(i: int):
        if not shared_prefix:
            return ("default",
                    rng.integers(0, cfg.vocab_size,
                                 size=(prompt_len,)).tolist())
        tenant = f"t{i % tenants}"
        tail = rng.integers(0, cfg.vocab_size,
                            size=(prompt_len - shared_len,)).tolist()
        return tenant, pools[tenant] + tail

    max_ctx = prompt_len + gen_tokens + (draft_k + 1 if speculative
                                         else 0) + 8
    per_seq = -(-max_ctx // block_size)
    num_blocks = clients * per_seq \
        + tenants * (-(-prompt_len // block_size)) + 1

    def factory(name: str) -> ContinuousBatchScheduler:
        eng_cfg = RaggedInferenceEngineConfig.from_dict({
            "state_manager": {"max_ragged_batch_size": 512,
                              "max_ragged_sequence_count": clients,
                              "max_context": max_ctx},
            "kv_cache": {"block_size": block_size,
                         "num_blocks": num_blocks,
                         **({"enable_prefix_cache": True}
                            if shared_prefix else {})},
        })
        eng = InferenceEngineV2(RaggedLlama(cfg, block_size), params,
                                eng_cfg)
        spec = SpeculativeConfig(
            draft_k=draft_k,
            drafter=make_self_drafter(eng)) if speculative else None
        return ContinuousBatchScheduler(eng, speculative=spec)

    if disaggregate:
        p, d = (int(x) for x in disaggregate.split(":"))
        fleet = ServingFleet(factory, prefill_replicas=p,
                             decode_replicas=d)
        decode_replicas = d
    else:
        fleet = ServingFleet(factory, replicas=n_replicas)
        decode_replicas = n_replicas

    sampling = SamplingParams(greedy=True, max_new_tokens=gen_tokens)

    # warmup: one small burst through every pool so the prefill buckets,
    # decode programs, and (disaggregated) the KV-inject put tail are all
    # compiled before the clock starts
    n_warm = min(clients, 4)
    for i in range(n_warm):
        fleet.submit(make_prompt(i)[1], tenant="warm", sampling=sampling)
    fleet.run_until_idle(max_ticks=20000)
    warm_handoffs = len(fleet.metrics.handoff_latency_s)

    arrivals = np.cumsum(rng.exponential(1.0 / rate_rps,
                                         size=n_requests))
    frs = []
    total_prompt_tokens = 0
    t0 = time.perf_counter()
    while len(frs) < n_requests or fleet.num_pending:
        now = time.perf_counter() - t0
        while len(frs) < n_requests and arrivals[len(frs)] <= now:
            tenant, prompt = make_prompt(len(frs))
            total_prompt_tokens += len(prompt)
            frs.append(fleet.submit(prompt, tenant=tenant,
                                    sampling=sampling))
        if fleet.num_pending:
            fleet.step()
        elif len(frs) < n_requests:
            time.sleep(min(arrivals[len(frs)] - now, 0.005))
    wall = time.perf_counter() - t0

    bad = [fr for fr in frs if fr.state != "finished"]
    assert not bad, [(fr.uid, fr.state, fr.finish_reason) for fr in bad]
    tokens = sum(len(fr.tokens) for fr in frs)
    goodput = tokens / wall
    ttft_ms = [1000 * fr.ttft for fr in frs if fr.ttft is not None]
    tpot_ms = [1000 * fr.tpot for fr in frs if fr.tpot is not None]
    lat = list(fleet.metrics.handoff_latency_s)[warm_handoffs:]

    n_params = sum(int(np.prod(l.shape))
                   for l in jax.tree_util.tree_leaves(params))
    roofline_tok_s = decode_replicas * clients * \
        hbm_bandwidth_bytes_per_s() / (n_params * 2)
    snap = fleet.snapshot()
    pct = lambda v, q: (float(np.percentile(v, q)) if v else 0.0)  # noqa: E731

    spec_extra = _spec_extra(
        [rep.scheduler for _pool, rep in fleet.pool_members()],
        draft_k) if speculative else {}

    return {
        "metric": "serving_fleet_goodput_tokens_per_sec",
        "value": round(goodput, 1),
        "unit": "tokens/s",
        "vs_baseline": round(goodput / (0.5 * roofline_tok_s), 4),
        "extra": {
            **spec_extra,
            "replicas": int(snap["fleet/replicas"]),
            "mode": (f"disaggregated {disaggregate}" if disaggregate
                     else f"colocated x{n_replicas}"),
            "shared_prefix": bool(shared_prefix),
            "n_requests": n_requests,
            "rate_rps": rate_rps,
            "prompt_len": prompt_len,
            "gen_tokens": gen_tokens,
            "p50_ttft_ms": round(pct(ttft_ms, 50), 2),
            "p95_ttft_ms": round(pct(ttft_ms, 95), 2),
            "p50_tpot_ms": round(pct(tpot_ms, 50), 3),
            "p95_tpot_ms": round(pct(tpot_ms, 95), 3),
            "handoffs": int(snap["fleet/handoffs"]),
            "p50_handoff_ms": round(1000 * pct(lat, 50), 3),
            "p95_handoff_ms": round(1000 * pct(lat, 95), 3),
            "sched_preemptions": int(snap["fleet/preemptions"]),
            "wall_s": round(wall, 2),
            "platform": jax.devices()[0].platform,
        },
    }


def _cli_str(flag: str, default):
    """Parse ``--flag=X`` or ``--flag X`` from argv."""
    for i, a in enumerate(sys.argv):
        if a.startswith(flag + "="):
            return a.split("=", 1)[1]
        if a == flag and i + 1 < len(sys.argv):
            return sys.argv[i + 1]
    return default


def _cli_float(flag: str, default: float) -> float:
    val = _cli_str(flag, None)
    return default if val is None else float(val)


if __name__ == "__main__":
    _shared_prefix = "--shared-prefix" in sys.argv or any(
        a.startswith("--shared-prefix-ratio") for a in sys.argv)
    _fleet = any(a == "--fleet" or a.startswith("--fleet=")
                 for a in sys.argv)
    _session_mix = "--session-mix" in sys.argv
    _disagg = _cli_str("--disaggregate", None)
    if _disagg is not None and not _fleet:
        raise SystemExit("bench_serving: --disaggregate P:D requires "
                         "--fleet N")
    if _disagg is not None and _session_mix:
        raise SystemExit("bench_serving: --session-mix composes with "
                         "--fleet N but not --disaggregate")
    _speculative = "--speculative" in sys.argv
    if _speculative and _session_mix:
        raise SystemExit("bench_serving: --session-mix does not compose "
                         "with --speculative")
    _trace_out = _cli_str("--trace", None)
    if _trace_out is not None and "--scheduler" not in sys.argv:
        raise SystemExit("bench_serving: --trace OUT requires "
                         "--scheduler (the traced decode A/B mode)")
    _draft_k_given = any(a == "--draft-k" or a.startswith("--draft-k=")
                         for a in sys.argv)
    _draft_k = int(_cli_float("--draft-k", 4))
    if _draft_k_given and not _speculative:
        raise SystemExit("bench_serving: --draft-k K requires "
                         "--speculative")
    # --shared-prefix and --speculative compose with --fleet (they select
    # the fleet's workload / decode mode) and with each other;
    # --session-mix composes with --shared-prefix and --fleet; every
    # other pairing is a conflict
    _modes = [f for f, on in [("--7b", "--7b" in sys.argv),
                              ("--scheduler", "--scheduler" in sys.argv),
                              ("--session-mix", _session_mix),
                              ("--fleet", _fleet and not _session_mix),
                              ("--shared-prefix",
                               _shared_prefix and not _fleet
                               and not _session_mix),
                              ("--speculative",
                               _speculative and not _fleet
                               and not _shared_prefix)] if on]
    if len(_modes) > 1:
        raise SystemExit(f"bench_serving: pick one mode, got {_modes}")
    # every mode needs the chip: a number from XLA's CPU backend or the
    # Pallas interpreter is never printed under a device metric's name,
    # and any failure below is a traceback and a non-zero exit, not an
    # error record
    from deepspeed_tpu.utils.compile_cache import enable_compile_cache
    from deepspeed_tpu.utils.platform import require_tpu

    require_tpu("bench_serving")
    enable_compile_cache()
    if "--7b" in sys.argv:
        print(json.dumps(measure_7b()))
    elif _session_mix:
        try:
            # default 2 covers bare "--fleet" as the LAST argv token
            # (no following value -> _cli_float's default)
            _sm_fleet = (int(_cli_float("--fleet", 2)) or 2) \
                if _fleet else None
        except ValueError:
            _sm_fleet = 2        # bare "--fleet" next to another flag
        print(json.dumps(measure_session_mix(
            idle_fraction=_cli_float("--idle-fraction", 0.5),
            resume_cadence=int(_cli_float("--resume-cadence", 3)),
            max_sessions=int(_cli_float("--max-sessions", 36)),
            shared_prefix=_shared_prefix,
            shared_prefix_ratio=_cli_float("--shared-prefix-ratio",
                                           0.5),
            fleet=_sm_fleet)))
    elif "--scheduler" in sys.argv:
        print(json.dumps(measure_scheduler(trace_out=_trace_out)))
    elif _fleet:
        try:
            _n_replicas = int(_cli_float("--fleet", 2))
        except ValueError:
            _n_replicas = 2      # bare "--fleet" next to another flag
        print(json.dumps(measure_fleet(
            n_replicas=_n_replicas,
            disaggregate=_disagg,
            shared_prefix=_shared_prefix,
            shared_prefix_ratio=_cli_float("--shared-prefix-ratio",
                                           0.9),
            speculative=_speculative, draft_k=_draft_k)))
    elif _shared_prefix:
        print(json.dumps(measure_shared_prefix(
            shared_prefix_ratio=_cli_float("--shared-prefix-ratio",
                                           0.9),
            speculative=_speculative, draft_k=_draft_k)))
    elif _speculative:
        print(json.dumps(measure_speculative(draft_k=_draft_k)))
    else:
        main()
