"""Device-time comparison: grouped GEMM MoE FFN vs dense all-experts.

Mixtral-shaped (E=8, top-2): dense computes every expert over every token
(E/k = 4x the FLOPs) and materialises [E, T, F] intermediates (E/k = 4x
the activation bytes). Serial dependency chains + two-point measurement
subtract the fixed cost of each run's closing sync (see bench_serving.py).

Measured on v5e (2026-07): grouped 1.3/2.5 ms vs dense 2.2/4.0 ms at
T=2048/4096 — a 1.6-1.7x wall win; the dense path is itself HBM-bound on
its ExF intermediates, so the 4x FLOP reduction does not all appear as
wall time on one chip, while the 4x intermediate-memory reduction does
(the training-relevant half of the Megablocks argument).
"""
import time

import numpy as np


def run(T):
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.ops.grouped_gemm import grouped_moe_ffn

    H, F, E, K = 1024, 3584, 8, 2
    rng = np.random.default_rng(0)
    x0 = jnp.asarray(rng.standard_normal((T, H)) * 0.02, jnp.bfloat16)
    wg = jnp.asarray(rng.standard_normal((E, H, F)) * 0.02, jnp.bfloat16)
    wu = jnp.asarray(rng.standard_normal((E, H, F)) * 0.02, jnp.bfloat16)
    wd = jnp.asarray(rng.standard_normal((E, F, H)) * 0.02, jnp.bfloat16)
    router = jnp.asarray(rng.standard_normal((H, E)) * 0.1, jnp.bfloat16)

    from deepspeed_tpu.ops.grouped_gemm import exact_topk_routing

    def route(x):
        return exact_topk_routing(
            x.astype(jnp.float32) @ router.astype(jnp.float32), K)

    @jax.jit
    def grouped_step(x):
        topi, topw = route(x)
        y = grouped_moe_ffn(x, topi, topw.astype(x.dtype), wg, wu, wd)
        return x + 0.01 * y        # serial dependency for chaining

    @jax.jit
    def dense_step(x):
        topi, topw = route(x)
        comb = jnp.sum(jax.nn.one_hot(topi, E, dtype=x.dtype)
                       * topw[..., None].astype(x.dtype), axis=1)
        h = jax.nn.silu(jnp.einsum("th,ehf->etf", x, wg)) * \
            jnp.einsum("th,ehf->etf", x, wu)
        y = jnp.einsum("etf,efh,te->th", h, wd, comb)
        return x + 0.01 * y

    def chain_time(f, n):
        t0 = time.perf_counter()
        y = x0
        for _ in range(n):
            y = f(y)
        jax.device_get(jnp.sum(y.astype(jnp.float32)))
        return time.perf_counter() - t0

    # warm/compile both, then interleave reps so drift hits both equally
    for f in (grouped_step, dense_step):
        chain_time(f, 4)
    times = {"grouped": {}, "dense": {}}
    for _ in range(4):
        for name, f in (("grouped", grouped_step), ("dense", dense_step)):
            for n in (16, 96):
                t = chain_time(f, n)
                times[name][n] = min(times[name].get(n, t), t)
    out = {}
    for name in ("grouped", "dense"):
        per = (times[name][96] - times[name][16]) / 80
        out[name] = per
        print(f"{name}: {per*1e3:.3f} ms/step "
              f"(t16={times[name][16]*1e3:.1f} "
              f"t96={times[name][96]*1e3:.1f})")
    print(f"speedup: {out['dense'] / out['grouped']:.2f}x "
          f"(E/k roofline = {E/K:.0f}x)")
    np.testing.assert_allclose(
        np.asarray(jax.device_get(grouped_step(x0))).astype(np.float32),
        np.asarray(jax.device_get(dense_step(x0))).astype(np.float32),
        atol=0.35, rtol=0.1)
    print("parity ok (bf16 tolerance)")


def main():
    from deepspeed_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    for t in (2048, 4096):
        print(f"--- T={t}")
        run(t)


if __name__ == "__main__":
    main()
