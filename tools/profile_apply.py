"""Isolate the optimizer-apply cost of the 125M bench step."""

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
    state = engine.state
    params = state["params"]
    key = jax.random.key(0)
    lr = jnp.asarray(1e-4, jnp.float32)

    # A: grads only (forces grad materialisation as outputs)
    micro_grads = engine._make_micro_grads()
    ga = jax.jit(lambda p, s, r, i: micro_grads(p, s, r, (i, i)))
    t_a, _ = timeit(ga, params, state["loss_scale"], key, jnp.asarray(ids))
    print(f"micro grads only:        {t_a:8.2f} ms")

    # B: full fused (non-donating copy for repeat timing)
    engine._build_fused_step()
    apply_step = engine._make_apply_step()

    def fused_nodonate(st, lr, r, i):
        grads, loss = micro_grads(st["params"], st["loss_scale"], r, (i, i))
        new_state, gnorm, overflow = apply_step(st, lr, grads=grads)
        return new_state["master"], loss

    jb = jax.jit(fused_nodonate)
    t_b, _ = timeit(jb, state, lr, key, jnp.asarray(ids))
    print(f"fused (no donate):       {t_b:8.2f} ms")

    # C: pure adam update traffic: read g,m,v,master; write m,v,master,params
    g_tree = jax.tree.map(lambda p: jnp.ones(p.shape, jnp.bfloat16), params)
    master = state["master"]
    m = engine.state["opt"]["m"]
    v = engine.state["opt"]["v"]

    def adam(g, m, v, p):
        def upd(g, m, v, p):
            g = g.astype(jnp.float32)
            m2 = 0.9 * m + 0.1 * g
            v2 = 0.999 * v + 0.001 * g * g
            p2 = p - 1e-4 * m2 / (jnp.sqrt(v2) + 1e-8)
            return p2, m2, v2, p2.astype(jnp.bfloat16)

        out = jax.tree.map(upd, g, m, v, p)
        is_t = lambda x: isinstance(x, tuple)
        pick = lambda i: jax.tree.map(lambda o: o[i], out, is_leaf=is_t)
        return pick(0), pick(1), pick(2), pick(3)

    jc = jax.jit(adam)
    t_c, _ = timeit(jc, g_tree, m, v, master)
    print(f"pure adam update:        {t_c:8.2f} ms")

    # D: adam + global-norm clip (two passes over grads)
    def adam_clip(g, m, v, p):
        sumsq = sum(jnp.sum(jnp.square(x.astype(jnp.float32)))
                    for x in jax.tree.leaves(g))
        coef = jnp.minimum(1.0, 1.0 / (jnp.sqrt(sumsq) + 1e-6))
        g = jax.tree.map(lambda x: x * coef, g)
        return adam(g, m, v, p)

    jd = jax.jit(adam_clip)
    t_d, _ = timeit(jd, g_tree, m, v, master)
    print(f"adam + gnorm clip:       {t_d:8.2f} ms")

    gb = 134.11e6 * (4 * 3 * 2 + 2 + 2) / 1e9
    print(f"\n(min traffic ~{gb:.1f} GB -> {gb/0.819:.1f} ms at 819 GB/s)")


if __name__ == "__main__":
    main()
