"""Compare flash-attention variants: 12 scanned layers in ONE dispatch."""

from __future__ import annotations

import functools
import time

import jax
import jax.numpy as jnp

from deepspeed_tpu.ops.flash_attention import flash_attention
from deepspeed_tpu.ops.attention import _xla_attention


def sync(x):
    jax.block_until_ready(x)


def timeit(fn, *args, iters=10):
    out = fn(*args)
    sync(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    sync(out)
    return (time.perf_counter() - t0) / iters * 1000, out


def bench(name, attn):
    mb, seq, h, d = 8, 1024, 12, 64
    q = jax.random.normal(jax.random.key(0), (mb, seq, h, d), jnp.bfloat16)

    def loss(q_):
        def body(x, _):
            o = attn(x, x, x)
            return o.astype(jnp.bfloat16), ()

        y, _ = jax.lax.scan(body, q_, None, length=12)
        return jnp.sum(y.astype(jnp.float32)) * 1e-6

    g = jax.jit(jax.grad(loss))
    try:
        t, _ = timeit(g, q)
        print(f"{name:40s}: {t:7.2f} ms (12-layer fwd+bwd)")
        return t
    except Exception as e:  # noqa: BLE001
        print(f"{name:40s}: FAILED {type(e).__name__}: {str(e)[:160]}")
        return None


def main():
    from deepspeed_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    for bq, bk in ((512, 1024), (512, 512), (256, 512), (256, 256),
                   (128, 256), (128, 128)):
        bench(f"ours bq={bq} bk={bk}",
              functools.partial(flash_attention, causal=True,
                                block_q=bq, block_k=bk))

    bench("xla dense", functools.partial(
        _xla_attention, causal=True, mask=None, scale=None))

    # jax's shipped TPU flash kernel (library call, perf bound reference)
    try:
        from jax.experimental.pallas.ops.tpu.flash_attention import (
            flash_attention as jax_flash, BlockSizes)

        def jf(q, k, v):
            # jax kernel wants [B,H,S,D]
            qt = q.transpose(0, 2, 1, 3)
            o = jax_flash(qt, qt, qt, causal=True,
                          sm_scale=1.0 / (q.shape[-1] ** 0.5))
            return o.transpose(0, 2, 1, 3)

        bench("jax library flash", jf)
    except Exception as e:  # noqa: BLE001
        print(f"jax library flash unavailable: {e}")


if __name__ == "__main__":
    main()
