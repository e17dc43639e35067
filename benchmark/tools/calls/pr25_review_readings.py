"""One-off of PR 25's review round (ran from a checkout's root on the chip): the
second readings the review asked for, each beside the limit it belongs to.

    python3 benchmark/tools/calls/pr25_review_readings.py

1. ``moe/combine`` both ways at the cell's row counts: the unsort by gather + weighted
   sum that ``grouped_moe_ffn`` has since this PR against the parent's scatter-add over
   the token index (both written out here; ten layers a call, as a forward has).
2. ``GMM_TOL``: the kernel against ``gmm_reference`` (sound), and ``gmm_reference``
   against itself with bf16 partial sums over 16 slices of K (the nearest precision
   below float32 accumulation).
3. ``ROUTER_AGREE_FLOOR``: ``moe_router`` (float32 products) and the same matmul at the
   default precision against float64 on the host, on float32 inputs and on bf16 values.
4. ``MOE_LOGIT_TOL``: the depth-2 OLMoE-width engine, grouped against dense (sound), and
   grouped against grouped with the top-8 weights renormalised (a fault).
"""
import dataclasses
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.getcwd())
import jax                                               # noqa: E402
import jax.numpy as jnp                                  # noqa: E402

import chip_smoke                                        # noqa: E402
from deepspeed_tpu.inference.v2 import (                 # noqa: E402
    InferenceEngineV2, RaggedInferenceEngineConfig)
from deepspeed_tpu.inference.v2.model_implementations.ragged_mixtral import (  # noqa: E402
    RaggedMixtral, moe_router)
from deepspeed_tpu.models.mixtral import (               # noqa: E402
    MixtralConfig, MixtralForCausalLM)
from deepspeed_tpu.ops.grouped_gemm import (             # noqa: E402
    _pick_tiles, exact_topk_routing, gmm, gmm_reference)

E, K, H, F, LAYERS = 64, 8, 2048, 1024, 10
rng = np.random.default_rng(25)


def timed(fn, *args, n=30):
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(n):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / n * 1e3


# 1. combine
def combine_gather(down, dest, topw):
    t = topw.shape[0]
    back = down[dest].astype(jnp.float32).reshape(t, K, H)
    return jnp.sum(back * topw.astype(jnp.float32)[..., None],
                   axis=1).astype(down.dtype)


def combine_scatter(down, order, topw):
    t = topw.shape[0]
    wflat = topw.reshape(-1)[order].astype(jnp.float32)
    return jnp.zeros((t, H), jnp.float32).at[order // K].add(
        down.astype(jnp.float32) * wflat[:, None]).astype(down.dtype)


def ten_layers(one):
    return jax.jit(lambda downs, idx, topw: jax.lax.scan(
        lambda acc, d: (acc + one(d, idx, topw), None),
        jnp.zeros((topw.shape[0], H), jnp.bfloat16), downs)[0])


for t in (32, 160, 544, 1056):
    m = t * K
    dest = rng.permutation(m).astype(np.int32)
    order = np.argsort(dest).astype(np.int32)
    downs = jnp.asarray(rng.standard_normal((LAYERS, m, H)), jnp.bfloat16)
    topw = jnp.asarray(rng.random((t, K)) / K, jnp.bfloat16)
    a = ten_layers(combine_gather)(downs, jnp.asarray(dest), topw)
    b = ten_layers(combine_scatter)(downs, jnp.asarray(order), topw)
    diff = float(jnp.max(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32))))
    print(f"combine, {t} tokens x {K} = {m} routed rows, {LAYERS} layers: gather + sum "
          f"{timed(ten_layers(combine_gather), downs, jnp.asarray(dest), topw):.3f} ms, "
          f"scatter-add {timed(ten_layers(combine_scatter), downs, jnp.asarray(order), topw):.3f}"
          f" ms; largest difference {diff:.4g}", flush=True)
    del downs

# 2. GMM_TOL
for m in (256, 4352):
    group_sizes = rng.multinomial(m, np.full(E, 1.0 / E))
    tm, tn = _pick_tiles(m, H, F)
    lhs = jnp.asarray(rng.standard_normal((m, H)), jnp.bfloat16)
    rhs = jnp.asarray(rng.standard_normal((E, H, F)) * H ** -0.5, jnp.bfloat16)
    gs = jnp.asarray(group_sizes, jnp.int32)
    want = np.asarray(gmm_reference(lhs, rhs, gs), np.float32)
    got = np.asarray(gmm(lhs, rhs, gs, tm, tn, False), np.float32)
    low = jnp.zeros((m, F), jnp.bfloat16)
    for s in range(0, H, H // 16):       # bf16 partial sums, summed in bf16
        sl = slice(s, s + H // 16)
        low = low + gmm_reference(lhs[:, sl], rhs[:, sl], gs)
    scale = np.max(np.abs(want))
    print(f"gmm, {m} rows, tiles {(tm, tn)}: kernel against gmm_reference "
          f"{np.max(np.abs(got - want)) / scale:.6f} of the largest value; gmm_reference "
          f"with bf16 partial sums over 16 slices of K against itself "
          f"{np.max(np.abs(np.asarray(low, np.float32) - want)) / scale:.6f}", flush=True)
    del lhs, rhs

# 3. the router alone
def router_default(x, wg):
    logits = jnp.matmul(x.astype(jnp.float32), wg.astype(jnp.float32))
    return exact_topk_routing(logits, K, False)[0]


x32 = rng.standard_normal((4352, H)).astype(np.float32)
wg32 = (rng.standard_normal((H, E)) * H ** -0.5).astype(np.float32)
for what, x, wg in (("float32 values", x32, wg32),
                    ("bf16 values", np.asarray(jnp.asarray(x32, jnp.bfloat16), np.float32),
                     np.asarray(jnp.asarray(wg32, jnp.bfloat16), np.float32))):
    want = np.sort(np.argsort(-(x.astype(np.float64) @ wg.astype(np.float64)),
                              -1)[:, :K], -1)
    high = np.sort(np.asarray(jax.jit(
        lambda a, b: moe_router(a, b, K, False)[0])(x, wg)), -1)
    low = np.sort(np.asarray(jax.jit(router_default)(x, wg)), -1)
    print(f"router, {x.shape[0]} rows of {what}: same top-{K} set as float64 for "
          f"{np.mean(np.all(high == want, -1)):.5f} of the rows with moe_router "
          f"(Precision.HIGHEST), {np.mean(np.all(low == want, -1)):.5f} at the default "
          f"precision", flush=True)

# 4. the depth-2 engine
cfg = MixtralConfig.olmoe_1b_7b(num_hidden_layers=2, dtype=jnp.bfloat16)
params = chip_smoke._seeded_bf16_params(cfg, model_cls=MixtralForCausalLM)
ids = rng.integers(0, cfg.vocab_size, size=(304,))


class DenseOracle(RaggedMixtral):
    grouped = False


def engine_logits(model):
    eng_cfg = RaggedInferenceEngineConfig.from_dict({
        "state_manager": {"max_ragged_batch_size": 512,
                          "max_ragged_sequence_count": 8, "max_context": 384},
        "kv_cache": {"block_size": 128, "num_blocks": 8}})
    engine = InferenceEngineV2(model, params, eng_cfg)
    rows = [np.asarray(engine.put([1], [ids[:300].tolist()])[1], np.float32)]
    for t in ids[300:]:
        rows.append(np.asarray(jax.device_get(
            engine.decode_step([1], [int(t)])), np.float32)[0])
    return np.stack(rows)


sound = engine_logits(RaggedMixtral(cfg, 128))
dense = engine_logits(DenseOracle(cfg, 128))
fault = engine_logits(RaggedMixtral(
    dataclasses.replace(cfg, norm_topk_prob=True), 128))
scale = np.max(np.abs(dense))
print(f"depth-2 engine: grouped against dense {np.max(np.abs(sound - dense)) / scale:.5f} "
      f"of the largest logit; grouped with renormalised top-{K} weights against dense "
      f"{np.max(np.abs(fault - dense)) / scale:.5f}", flush=True)
