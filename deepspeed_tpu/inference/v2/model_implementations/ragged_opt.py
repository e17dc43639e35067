"""Ragged (paged-KV) OPT forward for the FastGen engine.

Reference analog: ``inference/v2/model_implementations/opt/`` — OPT is
the reference family that stresses NON-rotary assumptions: positions
enter through a LEARNED embedding (with the characteristic offset of 2),
projections carry biases, layer norms are pre-LN LayerNorms with biases,
and the MLP is ReLU.  The paged-KV/attention machinery is shared with
RaggedLlama (`_paged_attention` consumes the identical metadata); the
param tree is EXACTLY :class:`models.opt.OPTForCausalLM`'s, so training
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
    insert_kv,
)
from deepspeed_tpu.models.opt import OPT_POSITION_OFFSET, OPTConfig


def _dense(x, p, dt):
    return x @ p["kernel"].astype(dt) + p["bias"].astype(dt)


class RaggedOPT:
    """Callable ragged forward bound to an :class:`OPTConfig`."""

    def __init__(self, config: OPTConfig, block_size: int):
        self.config = config
        self.block_size = block_size
        self.tp = 1

    @property
    def num_layers(self):
        return self.config.num_hidden_layers

    @property
    def num_kv_heads(self):
        return self.config.num_attention_heads  # MHA

    @property
    def head_dim(self):
        return self.config.head_dim

    @property
    def max_positions(self):
        """Learned position table size — the engine validates its
        max_context against this (positions past the table would
        silently alias the last row otherwise)."""
        return self.config.max_position_embeddings

    def __call__(self, params: Dict[str, Any], kv_cache: Dict[str, Any],
                 batch: Dict[str, jax.Array], prefill_tile=None,
                 decode=False):
        """Returns ``(logits [S, vocab], new_kv_cache)``."""
        cfg = self.config
        dt = cfg.dtype
        token_ids = batch["token_ids"]            # [T]
        token_pos = batch["token_pos"]            # [T]
        kv_dest = batch["kv_dest"]
        h, d = cfg.num_attention_heads, cfg.head_dim

        emb = params["embed_tokens"]["embedding"].astype(dt)
        # learned positions with offset 2; tile-aligned pads carry pos -1
        # -> clamp to a valid row (their KV lands in the trash block)
        pos_emb = params["embed_positions"]["embedding"].astype(dt)
        pos_idx = jnp.clip(token_pos, 0, pos_emb.shape[0]
                           - 1 - OPT_POSITION_OFFSET) + OPT_POSITION_OFFSET
        x = emb[token_ids] + pos_emb[pos_idx]                  # [T, H]

        new_cache = {}
        for i in range(cfg.num_hidden_layers):
            lp = params[f"layers_{i}"]
            residual = x
            xa = _layer_norm(x, lp["self_attn_layer_norm"],
                             cfg.layer_norm_eps).astype(dt) \
                if cfg.do_layer_norm_before else x
            at = lp["self_attn"]
            q = _dense(xa, at["q_proj"], dt).reshape(-1, h, d)
            k = _dense(xa, at["k_proj"], dt).reshape(-1, h, d)
            v = _dense(xa, at["v_proj"], dt).reshape(-1, h, d)
            lc = kv_cache[f"layer_{i}"]
            k_pool, v_pool = insert_kv(lc, kv_dest, k, v)
            new_cache[f"layer_{i}"] = {"k": k_pool, "v": v_pool}
            out = _paged_attention(q, k_pool, v_pool, batch,
                                   self.block_size,
                                   prefill_tile=prefill_tile,
                                   decode_mode=decode)
            x = residual + _dense(out.reshape(-1, h * d), at["out_proj"],
                                  dt)
            if not cfg.do_layer_norm_before:
                x = _layer_norm(x, lp["self_attn_layer_norm"],
                                cfg.layer_norm_eps).astype(dt)
            residual = x
            xm = _layer_norm(x, lp["final_layer_norm"],
                             cfg.layer_norm_eps).astype(dt) \
                if cfg.do_layer_norm_before else x
            xm = jax.nn.relu(_dense(xm, lp["fc1"], dt))
            x = residual + _dense(xm, lp["fc2"], dt)
            if not cfg.do_layer_norm_before:
                x = _layer_norm(x, lp["final_layer_norm"],
                                cfg.layer_norm_eps).astype(dt)
        if cfg.do_layer_norm_before:
            x = _layer_norm(x, params["final_layer_norm"],
                            cfg.layer_norm_eps)
        # tied unembedding in compute dtype (matches models/opt.py's
        # flax Embed.attend promotion); slot rows gathered BEFORE the
        # vocab matmul so prefill buckets don't unembed every token row
        x = x[batch["logits_idx"]]
        return x.astype(dt) @ emb.T, new_cache
