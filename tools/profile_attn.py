"""Attention-focused perf probes for the bench step."""

from __future__ import annotations

import functools
import time

import jax
import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.models import LlamaConfig, LlamaForCausalLM
from deepspeed_tpu.ops.attention import dot_product_attention


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


def main():
    from deepspeed_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    mb, seq = 8, 1024
    key = jax.random.key(0)
    q = jax.random.normal(key, (mb, seq, 12, 64), jnp.bfloat16)
    k = jax.random.normal(jax.random.key(1), q.shape, jnp.bfloat16)
    v = jax.random.normal(jax.random.key(2), q.shape, jnp.bfloat16)

    for impl in ("pallas", "xla"):
        att = jax.jit(functools.partial(
            dot_product_attention, causal=True, implementation=impl))
        t_f, _ = timeit(att, q, k, v)
        print(f"attn fwd only   ({impl:6s}): {t_f:7.3f} ms (x12={12*t_f:6.2f})")

        def att_loss(q_, k_, v_, impl=impl):
            o = dot_product_attention(q_, k_, v_, causal=True,
                                      implementation=impl)
            return jnp.sum(o.astype(jnp.float32)) * 1e-6

        ja = jax.jit(jax.grad(att_loss, argnums=(0, 1, 2)))
        t_b, _ = timeit(ja, q, k, v)
        print(f"attn fwd+bwd    ({impl:6s}): {t_b:7.3f} ms (x12={12*t_b:6.2f})")

    # full model with pinned attention impl
    cfg = LlamaConfig(vocab_size=32000, hidden_size=768,
                      intermediate_size=2048, num_hidden_layers=12,
                      num_attention_heads=12, num_key_value_heads=12,
                      max_position_embeddings=2048, dtype=jnp.bfloat16)
    rng = np.random.default_rng(0)
    ids = rng.integers(0, 32000, size=(mb, seq)).astype(np.int32)

    for impl in ("pallas", "xla"):
        model = LlamaForCausalLM(cfg, attention_fn=functools.partial(
            dot_product_attention, implementation=impl))
        params = model.init(jax.random.key(0), jnp.asarray(ids))["params"]
        params = jax.tree.map(lambda p: p.astype(jnp.bfloat16), params)

        def loss_fn(p, i, model=model):
            return model.apply({"params": p}, i, i)

        g = jax.jit(jax.value_and_grad(loss_fn))
        t, _ = timeit(g, params, jnp.asarray(ids))
        print(f"model fwd+bwd   ({impl:6s}): {t:7.2f} ms")


if __name__ == "__main__":
    main()
