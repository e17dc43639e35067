"""Mixtral-style MoE causal LM (parity target: reference MoE model support —
moe/layer.py integration + inference/v2/model_implementations/mixtral).

Llama backbone with the FFN replaced by a top-k routed MoE layer; expert
weights are stacked [E, ...] and sharded over the 'expert' mesh axis, so
expert parallelism is an all-to-all the compiler inserts.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from deepspeed_tpu.models.llama import (
    LlamaAttention,
    LlamaConfig,
    RMSNorm,
    cross_entropy_loss,
)
from deepspeed_tpu.moe.layer import MoE


@dataclasses.dataclass
class MixtralConfig(LlamaConfig):
    num_local_experts: int = 8
    num_experts_per_tok: int = 2
    router_aux_loss_coef: float = 0.02
    moe_capacity_factor: float = 1.25
    # HF ``norm_topk_prob``: the top-k router weights renormalised to sum
    # to one (Mixtral) or used as the softmax gave them (OLMoE: False).
    # Serving honours it always; the training gate on its dropless path,
    # which ``MixtralBlock`` takes for more than two experts a token (the
    # capacity gate knows k <= 2 and always renormalises).
    norm_topk_prob: bool = True

    @staticmethod
    def tiny(**kw) -> "MixtralConfig":
        base = dict(vocab_size=256, hidden_size=64, intermediate_size=128,
                    num_hidden_layers=2, num_attention_heads=4,
                    num_key_value_heads=4, max_position_embeddings=128,
                    num_local_experts=4, num_experts_per_tok=2)
        base.update(kw)
        return MixtralConfig(**base)

    @staticmethod
    def mixtral_8x7b(**kw) -> "MixtralConfig":
        base = dict(vocab_size=32000, hidden_size=4096,
                    intermediate_size=14336, num_hidden_layers=32,
                    num_attention_heads=32, num_key_value_heads=8,
                    num_local_experts=8, num_experts_per_tok=2,
                    rope_theta=1e6)
        base.update(kw)
        return MixtralConfig(**base)

    @staticmethod
    def olmoe_1b_7b(**kw) -> "MixtralConfig":
        """OLMoE-1B-7B (allenai, ``model_type: olmoe``): 64 experts of
        width 1024 at top-8 with unnormalised router weights, 16 KV heads,
        RMSNorm on the whole q and k projections."""
        base = dict(vocab_size=50304, hidden_size=2048,
                    intermediate_size=1024, num_hidden_layers=16,
                    num_attention_heads=16, num_key_value_heads=16,
                    max_position_embeddings=4096, rms_norm_eps=1e-5,
                    rope_theta=10000.0, num_local_experts=64,
                    num_experts_per_tok=8, qk_norm=True,
                    norm_topk_prob=False)
        base.update(kw)
        return MixtralConfig(**base)


MIXTRAL_PARTITION_RULES = [
    (r"embed_tokens/embedding", P("model", None)),
    (r"(q_proj|k_proj|v_proj)/kernel", P(None, "model")),
    (r"o_proj/kernel", P("model", None)),
    (r"experts/w_(gate|up)", P("expert", None, "model")),
    (r"experts/w_down", P("expert", "model", None)),
    (r"gate/wg/kernel", P()),
    (r"lm_head/kernel", P(None, "model")),
    (r".*norm.*", P()),
]


class MixtralBlock(nn.Module):
    config: MixtralConfig

    @nn.compact
    def __call__(self, x, positions, attention_fn=None, train: bool = True,
                 rng=None):
        cfg = self.config
        a, _ = LlamaAttention(cfg, name="self_attn")(
            RMSNorm(cfg.rms_norm_eps, name="input_layernorm")(x),
            positions, attention_fn)
        x = x + a
        moe_out, l_aux = MoE(
            hidden_size=cfg.hidden_size,
            intermediate_size=cfg.intermediate_size,
            num_experts=cfg.num_local_experts,
            k=cfg.num_experts_per_tok,
            capacity_factor=cfg.moe_capacity_factor,
            eval_capacity_factor=cfg.moe_capacity_factor,
            dtype=cfg.dtype, dropless=cfg.num_experts_per_tok > 2,
            norm_topk_prob=cfg.norm_topk_prob, name="block_sparse_moe")(
                RMSNorm(cfg.rms_norm_eps, name="post_attention_layernorm")(x),
                train=train, rng=rng)
        return x + moe_out, l_aux


class MixtralForCausalLM(nn.Module):
    config: MixtralConfig
    attention_fn: Any = None

    @property
    def partition_rules(self):
        return MIXTRAL_PARTITION_RULES

    @nn.compact
    def __call__(self, input_ids, labels=None, train: bool = True):
        cfg = self.config
        b, s = input_ids.shape
        positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None],
                                     (b, s))
        x = nn.Embed(cfg.vocab_size, cfg.hidden_size, dtype=cfg.dtype,
                     param_dtype=jnp.float32, name="embed_tokens")(input_ids)
        aux_total = jnp.float32(0.0)
        for i in range(cfg.num_hidden_layers):
            x, l_aux = MixtralBlock(cfg, name=f"layers_{i}")(
                x, positions, self.attention_fn, train)
            aux_total = aux_total + l_aux
        x = RMSNorm(cfg.rms_norm_eps, name="norm")(x)
        logits = nn.Dense(cfg.vocab_size, use_bias=False, dtype=cfg.dtype,
                          param_dtype=jnp.float32, name="lm_head")(x)
        if labels is None:
            return logits
        ce = cross_entropy_loss(logits, labels)
        return ce + cfg.router_aux_loss_coef * \
            (aux_total / cfg.num_hidden_layers)
