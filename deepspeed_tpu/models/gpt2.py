"""GPT-2 causal LM (parity target: the reference's gpt2/megatron containers
module_inject/containers/gpt2.py, megatron.py and the GPT-2 125M debug config
tests/small_model_debugging/).

Learned positional embeddings, pre-LayerNorm blocks, GELU MLP, tied
embedding/unembedding. Same engine contract as Llama: ``__call__(input_ids,
labels)`` returns the loss when labels are given.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from deepspeed_tpu.models.llama import cross_entropy_loss
from deepspeed_tpu.ops.attention import dot_product_attention


@dataclasses.dataclass
class GPT2Config:
    vocab_size: int = 50257
    hidden_size: int = 768
    num_hidden_layers: int = 12
    num_attention_heads: int = 12
    max_position_embeddings: int = 1024
    layer_norm_epsilon: float = 1e-5
    embd_pdrop: float = 0.0
    attn_pdrop: float = 0.0
    resid_pdrop: float = 0.0
    # HF `n_inner`: MLP width (None -> the GPT-2 default of 4*n_embd)
    intermediate_size: Any = None
    dtype: Any = jnp.bfloat16
    remat: bool = False

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def mlp_dim(self) -> int:
        return (self.intermediate_size if self.intermediate_size
                else 4 * self.hidden_size)

    @staticmethod
    def gpt2_125m(**kw) -> "GPT2Config":
        return GPT2Config(**kw)

    @staticmethod
    def tiny(**kw) -> "GPT2Config":
        base = dict(vocab_size=256, hidden_size=64, num_hidden_layers=2,
                    num_attention_heads=4, max_position_embeddings=128)
        base.update(kw)
        return GPT2Config(**base)


GPT2_PARTITION_RULES = [
    (r"wte/embedding", P("model", None)),
    (r"wpe/embedding", P()),
    (r"c_attn/kernel", P(None, "model")),
    (r"attn_out/kernel", P("model", None)),
    (r"c_fc/kernel", P(None, "model")),
    (r"c_proj/kernel", P("model", None)),
    (r".*(ln_1|ln_2|ln_f).*", P()),
]


class GPT2Block(nn.Module):
    config: GPT2Config

    @nn.compact
    def __call__(self, x, deterministic: bool = True):
        cfg = self.config
        h, d = cfg.num_attention_heads, cfg.head_dim
        ln = lambda name: nn.LayerNorm(epsilon=cfg.layer_norm_epsilon,
                                       dtype=cfg.dtype,
                                       param_dtype=jnp.float32, name=name)
        dense = lambda feats, name: nn.Dense(feats, dtype=cfg.dtype,
                                             param_dtype=jnp.float32, name=name)
        # device scopes h_<i>/attn and h_<i>/mlp (the sublayers are not
        # modules of their own here, so flax names only their Dense layers)
        with jax.named_scope("attn"):
            y = ln("ln_1")(x)
            qkv = dense(3 * cfg.hidden_size, "c_attn")(y)
            q, k, v = jnp.split(qkv, 3, axis=-1)
            reshape = lambda t: t.reshape(*t.shape[:2], h, d)
            out = dot_product_attention(reshape(q), reshape(k), reshape(v),
                                        causal=True)
            out = out.reshape(*x.shape[:2], cfg.hidden_size)
            out = dense(cfg.hidden_size, "attn_out")(out)
            if cfg.resid_pdrop > 0:
                out = nn.Dropout(cfg.resid_pdrop)(
                    out, deterministic=deterministic)
            x = x + out
        with jax.named_scope("mlp"):
            y = ln("ln_2")(x)
            y = dense(cfg.mlp_dim, "c_fc")(y)
            y = nn.gelu(y, approximate=True)
            y = dense(cfg.hidden_size, "c_proj")(y)
            if cfg.resid_pdrop > 0:
                y = nn.Dropout(cfg.resid_pdrop)(
                    y, deterministic=deterministic)
            return x + y


class GPT2LMHeadModel(nn.Module):
    config: GPT2Config

    @property
    def partition_rules(self):
        return GPT2_PARTITION_RULES

    @nn.compact
    def __call__(self, input_ids, labels=None, deterministic: bool = True):
        cfg = self.config
        b, s = input_ids.shape
        wte = nn.Embed(cfg.vocab_size, cfg.hidden_size, dtype=cfg.dtype,
                       param_dtype=jnp.float32, name="wte")
        wpe = nn.Embed(cfg.max_position_embeddings, cfg.hidden_size,
                       dtype=cfg.dtype, param_dtype=jnp.float32, name="wpe")
        x = wte(input_ids) + wpe(jnp.arange(s, dtype=jnp.int32)[None])
        if cfg.embd_pdrop > 0:
            x = nn.Dropout(cfg.embd_pdrop)(x, deterministic=deterministic)
        block = GPT2Block
        if cfg.remat:
            block = nn.remat(
                GPT2Block,
                policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
        for i in range(cfg.num_hidden_layers):
            x = block(cfg, name=f"h_{i}")(x, deterministic)
        x = nn.LayerNorm(epsilon=cfg.layer_norm_epsilon, dtype=cfg.dtype,
                         param_dtype=jnp.float32, name="ln_f")(x)
        if labels is None:
            return wte.attend(x.astype(cfg.dtype))
        # one device scope over the tied head and the loss, forward and
        # backward: the training step's vocabulary-sized work
        with jax.named_scope("lm_head_loss"):
            return cross_entropy_loss(wte.attend(x.astype(cfg.dtype)),
                                      labels)
