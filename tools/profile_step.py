"""Phase attribution for the bench.py training step.

Times each phase of the 125M-Llama step as its own (non-donating) jitted
program, each timing closed by block_until_ready. Run on the real chip:

    PYTHONPATH=. python tools/profile_step.py
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

import deepspeed_tpu
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


def main():
    from deepspeed_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    cfg_m = LlamaConfig(vocab_size=32000, hidden_size=768,
                        intermediate_size=2048, num_hidden_layers=12,
                        num_attention_heads=12, num_key_value_heads=12,
                        max_position_embeddings=2048, dtype=jnp.bfloat16)
    seq, mb = 1024, 8
    ds_config = {
        "train_micro_batch_size_per_gpu": mb,
        "gradient_accumulation_steps": 1,
        "optimizer": {"type": "AdamW", "params": {"lr": 1e-4}},
        "zero_optimization": {"stage": 1},
        "bf16": {"enabled": True},
        "gradient_clipping": 1.0,
    }
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=LlamaForCausalLM(cfg_m), config=ds_config)
    rng = np.random.default_rng(0)
    ids = rng.integers(0, cfg_m.vocab_size, size=(mb, seq)).astype(np.int32)

    engine.initialize_parameters(ids, ids)
    params = engine.state["params"]
    key = jax.random.key(0)

    apply_fn = engine._apply_fn

    # 1. forward only (loss)
    fwd = jax.jit(lambda p, i: apply_fn(p, i, i, rng=None, train=True))
    t_fwd, _ = timeit(fwd, params, ids)
    print(f"fwd only (loss):       {t_fwd:8.2f} ms")

    # 2. fwd+bwd (grads)
    def loss_fn(p, i):
        return apply_fn(p, i, i, rng=None, train=True)

    grad = jax.jit(lambda p, i: jax.value_and_grad(loss_fn)(p, i))
    t_g, _ = timeit(grad, params, ids)
    print(f"fwd+bwd:               {t_g:8.2f} ms")

    # 3. transformer stack only (logits, no labels -> no CE), fwd and fwd+bwd
    fwd_logits = jax.jit(lambda p, i: apply_fn(p, i, rng=None, train=True))
    t_fl, _ = timeit(fwd_logits, params, ids)
    print(f"fwd logits (no CE):    {t_fl:8.2f} ms")

    def logits_sum(p, i):
        return jnp.sum(apply_fn(p, i, rng=None, train=True)
                       .astype(jnp.float32)) * 1e-6

    g2 = jax.jit(jax.grad(logits_sum))
    t_g2, _ = timeit(g2, params, ids)
    print(f"fwd+bwd (sum logits):  {t_g2:8.2f} ms")

    # 4. attention alone, flash vs xla, fwd+bwd  [8,1024,12,64]
    from deepspeed_tpu.ops.attention import dot_product_attention

    q = jax.random.normal(key, (mb, seq, 12, 64), jnp.bfloat16)

    for impl in ("pallas", "xla"):
        def att_loss(q_, impl=impl):
            o = dot_product_attention(q_, q_, q_, causal=True,
                                      implementation=impl)
            return jnp.sum(o.astype(jnp.float32))

        ja = jax.jit(jax.grad(att_loss))
        try:
            t_att, _ = timeit(ja, q)
            print(f"attn x1 fwd+bwd ({impl:6s}): {t_att:7.3f} ms "
                  f"(x12 = {12*t_att:6.2f})")
        except Exception as e:  # noqa: BLE001
            print(f"attention ({impl}) failed: {type(e).__name__}: "
                  f"{str(e)[:200]}")

    # 5. lm_head + CE fwd+bwd at [8,1024,768] -> 32000
    x = jax.random.normal(key, (mb, seq, 768), jnp.bfloat16)
    w = jax.random.normal(key, (768, 32000), jnp.float32) * 0.02
    labels = jnp.asarray(ids)

    def head_ce(x, w, lab):
        logits = (x @ w.astype(jnp.bfloat16))[:, :-1].astype(jnp.float32)
        t = lab[:, 1:]
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, t[..., None], -1).squeeze(-1)
        return jnp.mean(logz - gold)

    jh = jax.jit(jax.value_and_grad(head_ce, argnums=(0, 1)))
    t_h, _ = timeit(jh, x, w, labels)
    print(f"lm_head+CE fwd+bwd:    {t_h:8.2f} ms")

    # 6. embed fwd+bwd at [8,1024] -> 768
    emb = jax.random.normal(key, (32000, 768), jnp.float32) * 0.02

    def embed_loss(e, i):
        return jnp.sum(e[i].astype(jnp.float32)) * 1e-6

    je = jax.jit(jax.grad(embed_loss))
    t_e, _ = timeit(je, emb, jnp.asarray(ids))
    print(f"embed fwd+bwd:         {t_e:8.2f} ms")

    # 7. projection-chain probe: 12 layers' worth of dense matmuls, fwd+bwd
    toks = mb * seq
    x2 = jax.random.normal(key, (toks, 768), jnp.bfloat16)
    key2 = jax.random.key(1)
    w768 = [jax.random.normal(key2, (768, 768), jnp.bfloat16)
            for _ in range(4 * 12)]
    wup = [jax.random.normal(key2, (768, 2048), jnp.bfloat16)
           for _ in range(2 * 12)]
    wdn = [jax.random.normal(key2, (2048, 768), jnp.bfloat16)
           for _ in range(12)]

    def chain(x, w768, wup, wdn):
        h = x
        for i in range(12):
            for j in range(4):
                h = h @ w768[4 * i + j] * 0.05
            a = h @ wup[2 * i] * 0.05
            b = h @ wup[2 * i + 1] * 0.05
            h = (a * b) @ wdn[i] * 0.05
        return jnp.sum(h.astype(jnp.float32)) * 1e-6

    jc = jax.jit(jax.grad(chain, argnums=(0,)))
    t_c, _ = timeit(jc, x2, w768, wup, wdn)
    fl = (sum(2 * toks * w.shape[0] * w.shape[1]
              for w in w768 + wup + wdn)) * 3
    print(f"proj chain fwd+bwd:    {t_c:8.2f} ms  "
          f"({fl/(t_c*1e-3)/1e12:6.1f} TF/s eff, "
          f"ideal {fl/197e12*1000:5.2f} ms)")

    n_params = sum(int(np.prod(l.shape))
                   for l in jax.tree.leaves(params))
    ideal = 6 * n_params * mb * seq / 197e12 * 1000
    print(f"\nideal 6ND fwd+bwd:     {ideal:8.2f} ms "
          f"(n={n_params/1e6:.1f}M, peak 197TF)")


if __name__ == "__main__":
    main()
