"""Ablation profiling: where does the 125M fwd+bwd time actually go.

Each variant is ONE jitted fwd+bwd program (dispatch overhead is constant
across variants, so deltas are real).
"""

from __future__ import annotations

import functools
import time

import jax
import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.models import LlamaConfig, LlamaForCausalLM


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


def measure(name, cfg, attention_fn=None, iters=10):
    mb, seq = 8, 1024
    rng = np.random.default_rng(0)
    ids = rng.integers(0, cfg.vocab_size, size=(mb, seq)).astype(np.int32)
    model = LlamaForCausalLM(cfg, attention_fn=attention_fn)
    params = model.init(jax.random.key(0), jnp.asarray(ids))["params"]
    params = jax.tree.map(lambda p: p.astype(jnp.bfloat16), params)

    def loss_fn(p, i):
        return model.apply({"params": p}, i, i)

    g = jax.jit(jax.value_and_grad(loss_fn))
    t, _ = timeit(g, params, jnp.asarray(ids), iters=iters)
    print(f"{name:42s}: {t:7.2f} ms")
    return t


def main():
    from deepspeed_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    base = dict(vocab_size=32000, hidden_size=768, intermediate_size=2048,
                num_hidden_layers=12, num_attention_heads=12,
                num_key_value_heads=12, max_position_embeddings=2048,
                dtype=jnp.bfloat16)

    t_full = measure("full (pallas attn)", LlamaConfig(**base))

    ident = lambda q, k, v, **kw: q
    t_noattn = measure("identity attention", LlamaConfig(**base),
                       attention_fn=ident)

    t_smallvocab = measure("vocab=512 (no head/CE cost)",
                           LlamaConfig(**{**base, "vocab_size": 512}))

    t_l6 = measure("6 layers", LlamaConfig(**{**base,
                                              "num_hidden_layers": 6}))

    from deepspeed_tpu.ops.attention import dot_product_attention

    t_xla = measure("xla attention", LlamaConfig(**base),
                    attention_fn=functools.partial(
                        dot_product_attention, implementation="xla"))

    print()
    print(f"attention total (full - identity):   {t_full - t_noattn:7.2f} ms")
    print(f"head+CE+embed (full - vocab512):     {t_full - t_smallvocab:7.2f} ms")
    print(f"per-6-layers slope (full - l6):      {t_full - t_l6:7.2f} ms")
    print(f"xla vs pallas attention:             {t_xla - t_full:7.2f} ms")


if __name__ == "__main__":
    main()
