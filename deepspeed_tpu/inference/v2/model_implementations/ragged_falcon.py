"""Ragged (paged-KV) Falcon forward for the FastGen engine.

Reference analog: ``inference/v2/model_implementations/falcon/`` — the
family that stresses the two assumptions the Llama-shaped serving code
bakes in: PARALLEL attention (attention and MLP branches both read the
same layer-norm output and both add into the residual) and multi-query
attention (a single shared KV head, so the blocked KV pool carries
``Hkv=1`` and GQA grouping runs at ``group == num_heads``).  The
reference likewise supports only ``parallel_attn`` (falcon/model.py:132).

Attention/paged-KV machinery is shared with RaggedLlama; the param tree
is EXACTLY :class:`models.falcon.FalconForCausalLM`'s, so training
checkpoints (and HF checkpoints via checkpoint/hf_loader.py) serve
directly.
"""

from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

from deepspeed_tpu.inference.v2.modules.attention import (
    _layer_norm,
    _paged_attention,
    _rotary,
    insert_kv,
)
from deepspeed_tpu.models.falcon import FalconConfig, split_fused_qkv
from deepspeed_tpu.models.llama import apply_rotary


class RaggedFalcon:
    """Callable ragged forward bound to a :class:`FalconConfig`."""

    def __init__(self, config: FalconConfig, block_size: int):
        self.config = config
        self.block_size = block_size
        self.tp = 1

    @property
    def num_layers(self):
        return self.config.num_hidden_layers

    @property
    def num_kv_heads(self):
        return self.config.num_kv_heads

    @property
    def head_dim(self):
        return self.config.head_dim

    def __call__(self, params: Dict[str, Any], kv_cache: Dict[str, Any],
                 batch: Dict[str, jax.Array], prefill_tile=None,
                 decode=False):
        """Returns ``(logits [S, vocab], new_kv_cache)``."""
        cfg = self.config
        dt = cfg.dtype
        token_ids = batch["token_ids"]
        token_pos = batch["token_pos"]
        kv_dest = batch["kv_dest"]
        h, hkv, d = (cfg.num_attention_heads, cfg.num_kv_heads,
                     cfg.head_dim)

        def dense(x, p):
            y = x @ p["kernel"].astype(dt)
            if "bias" in p:
                y = y + p["bias"].astype(dt)
            return y

        emb = params["word_embeddings"]["embedding"].astype(dt)
        x = emb[token_ids]                                      # [T, H]
        cos, sin = _rotary(token_pos, d, cfg.rope_theta)
        new_cache = {}
        for i in range(cfg.num_hidden_layers):
            lp = params[f"h_{i}"]
            ln = _layer_norm(x, lp["input_layernorm"],
                             cfg.layer_norm_epsilon).astype(dt)
            at = lp["self_attention"]
            qkv = dense(ln, at["query_key_value"])
            q, k, v = split_fused_qkv(qkv, h, hkv, d)
            q = apply_rotary(q, cos, sin)
            k = apply_rotary(k, cos, sin)
            lc = kv_cache[f"layer_{i}"]
            k_pool, v_pool = insert_kv(lc, kv_dest, k, v)
            new_cache[f"layer_{i}"] = {"k": k_pool, "v": v_pool}
            out = _paged_attention(q, k_pool, v_pool, batch,
                                   self.block_size,
                                   prefill_tile=prefill_tile,
                                   decode_mode=decode)
            attn = dense(out.reshape(-1, h * d), at["dense"])
            mlp = dense(jax.nn.gelu(
                dense(ln, lp["mlp"]["dense_h_to_4h"]),
                approximate=False), lp["mlp"]["dense_4h_to_h"])
            # parallel residual
            x = x + attn + mlp
        x = _layer_norm(x, params["ln_f"], cfg.layer_norm_epsilon)
        # tied unembedding; slot rows gathered BEFORE the vocab matmul so
        # prefill buckets don't unembed every token row
        x = x[batch["logits_idx"]]
        return x.astype(dt) @ emb.T, new_cache
