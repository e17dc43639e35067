"""Decode-attention crossover: dense-pool read vs the manual-DMA paged
kernel (VERDICT r4 weak #3 / next-round #3).

Sweeps (context length, pool size) at serving-representative shapes and
prints a table of per-step times for the three decode paths the engine
can take:

* dense  — masked dense attention over the WHOLE pool (one read of every
  pool row; bandwidth-optimal when the pool is tight around the live
  contexts, the round-4 default)
* gather — the [S, C, Hkv, D] XLA context gather (bounded by table
  extent, pays a materialised copy)
* kernel — ``paged_decode_attention``: per-sequence dynamic walk over
  live blocks with double-buffered HBM DMAs; reads Σ live-context bytes.

All timings amortise the per-dispatch cost with an in-graph
lax.fori_loop chain.  Run on a real chip:

    python tools/profile_decode_attn.py
"""

from __future__ import annotations

import sys
import time

sys.path.insert(0, ".")

import jax
import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.inference.v2.kernels.blocked_flash import (
    paged_decode_attention)
from deepspeed_tpu.inference.v2.model_implementations.ragged_llama import (
    _paged_attention)


def sync(x):
    jax.block_until_ready(x)


def chain(fn, q, k_pool, v_pool, n=20):
    """Amortised timing; pools ride as ARGUMENTS (a closure would bake
    them into the program as multi-hundred-MB constants)."""
    @jax.jit
    def run(q, k_pool, v_pool):
        def body(i, acc):
            y = fn(q + 0.0 * acc[:, :1, :1], k_pool, v_pool)
            return y
        return jax.lax.fori_loop(0, n, body, jnp.zeros_like(q))
    o = run(q, k_pool, v_pool)
    sync(o)
    best = 1e9
    for _ in range(3):
        t0 = time.perf_counter()
        o = run(q, k_pool, v_pool)
        sync(o)
        best = min(best, (time.perf_counter() - t0) / n * 1000)
    return best


def measure(S, ctx, pool_blocks, bs=128, h=32, hkv=32, d=128,
            dtype=jnp.bfloat16, layers=1):
    B = -(-ctx // bs) + 1
    rng = np.random.default_rng(0)
    key = jax.random.key(0)
    ks = jax.random.split(key, 3)
    rows = pool_blocks * bs
    k_pool = jax.random.normal(ks[0], (rows, hkv, d), dtype)
    v_pool = jax.random.normal(ks[1], (rows, hkv, d), dtype)
    # each sequence owns B random distinct blocks (1..pool-1; 0 = trash)
    tables = np.stack([rng.choice(pool_blocks - 1, B, replace=False) + 0
                       for _ in range(S)]) % pool_blocks
    tables = jnp.asarray(tables, jnp.int32)
    token_pos = jnp.full((S,), ctx - 1, jnp.int32)
    token_slot = jnp.arange(S, dtype=jnp.int32)
    q = jax.random.normal(ks[2], (S, h, d), dtype)
    batch = {"block_tables": tables, "token_slot": token_slot,
             "token_pos": token_pos}

    out = {}
    out["kernel"] = chain(lambda q, kp, vp: paged_decode_attention(
        q, kp, vp, tables, token_slot, token_pos,
        block_size=bs, interpret=False), q, k_pool, v_pool)
    # dense reads the whole pool regardless of table extent
    out["dense"] = chain(lambda q, kp, vp: _paged_attention(
        q, kp, vp, batch, bs, use_kernel=False,
        decode_mode=True, force_dense=True), q, k_pool, v_pool)
    out["gather"] = chain(lambda q, kp, vp: _paged_attention(
        q, kp, vp, batch, bs, use_kernel=False,
        decode_mode=True, force_dense=False), q, k_pool, v_pool)
    return out


def main():
    from deepspeed_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    print(f"platform: {jax.devices()[0].device_kind}")
    print(f"{'S':>3} {'ctx':>6} {'pool_blk':>8} | "
          f"{'kernel ms':>10} {'dense ms':>9} {'gather ms':>10}")
    # 7B-geometry kv (32 kv heads x 128) and 125M GQA kv (2 x 64)
    for (h, hkv, d, tag) in [(32, 32, 128, "7b"), (6, 2, 64, "125m")]:
        print(f"-- {tag}: H={h} Hkv={hkv} D={d}")
        for S, ctx, pool in [(8, 512, 33), (8, 2048, 136), (8, 2048, 512),
                             (8, 4096, 264), (32, 2048, 544),
                             (8, 512, 512)]:
            try:
                r = measure(S, ctx, pool, h=h, hkv=hkv, d=d)
                print(f"{S:>3} {ctx:>6} {pool:>8} | "
                      f"{r['kernel']:>10.3f} {r['dense']:>9.3f} "
                      f"{r['gather']:>10.3f}")
            except Exception as e:  # noqa: BLE001
                print(f"{S:>3} {ctx:>6} {pool:>8} | FAIL {str(e)[:60]}")


if __name__ == "__main__":
    main()
