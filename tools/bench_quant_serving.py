"""Decode-step time + HBM-resident weight bytes: bf16 vs int8 weight-only
serving (ops/quantized_matmul.py) on the 125M-GQA serving model."""
import time

import numpy as np


def main():
    from deepspeed_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.inference.v2 import (InferenceEngineV2,
                                            RaggedInferenceEngineConfig)
    from deepspeed_tpu.inference.v2.model_implementations import RaggedLlama
    from deepspeed_tpu.models import LlamaConfig, LlamaForCausalLM
    from deepspeed_tpu.runtime.weight_quantizer import WeightQuantization

    import os
    big = os.environ.get("QUANT_BENCH_BIG") == "1"
    cfg = LlamaConfig(vocab_size=32000,
                      hidden_size=2048 if big else 768,
                      intermediate_size=5632 if big else 2048,
                      num_hidden_layers=16 if big else 12,
                      num_attention_heads=16 if big else 6,
                      num_key_value_heads=4 if big else 2,
                      max_position_embeddings=2048, dtype=jnp.bfloat16)
    clients, prompt_len, bs = 8, 256, 128
    params = LlamaForCausalLM(cfg).init(
        jax.random.key(0), np.zeros((1, 8), np.int32))["params"]
    params = jax.tree.map(lambda p: p.astype(jnp.bfloat16), params)
    eng_cfg = RaggedInferenceEngineConfig.from_dict({
        "state_manager": {"max_ragged_batch_size": 512,
                          "max_ragged_sequence_count": clients,
                          "max_context": prompt_len + 300},
        "kv_cache": {"block_size": bs},
    })
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, size=(prompt_len,)).tolist()
               for _ in range(clients)]

    def measure(p, tag):
        eng = InferenceEngineV2(RaggedLlama(cfg, bs), p, eng_cfg)
        uids = list(range(clients))
        lg = eng.put(uids, prompts)
        start = [int(np.argmax(lg[u])) for u in uids]
        eng.decode_loop(uids, start, 16)   # warm both chunk programs
        t16 = t64 = float("inf")
        for _ in range(2):
            t0 = time.perf_counter()
            tk = eng.decode_loop(uids, start, 16)
            t16 = min(t16, time.perf_counter() - t0)
            t0 = time.perf_counter()
            tk = eng.decode_loop(uids, [int(tk[i, -1]) for i in
                                        range(clients)], 64)
            t64 = min(t64, time.perf_counter() - t0)
        marg = (t64 - t16) / 48
        wb = sum(l.nbytes for l in jax.tree_util.tree_leaves(p))
        print(f"{tag}: weight bytes {wb/1e6:.0f}MB, decode marginal "
              f"{marg*1e3:.3f} ms/step, first token {tk[0, 0]}")
        eng.flush(uids)
        return marg, tk[:, :4].copy()

    m_bf16, t1 = measure(params, "bf16   ")
    wq = WeightQuantization(quantize_bits=8, quantize_groups=64)
    qparams, n = wq.model_quantize(params, exclude=("embed",))
    m_int8, t2 = measure(qparams, f"int8({n:2d})")
    print(f"speedup {m_bf16 / m_int8:.2f}x; greedy tokens "
          f"{'MATCH' if np.array_equal(t1, t2) else 'differ (int8 quant)'}")


if __name__ == "__main__":
    main()
