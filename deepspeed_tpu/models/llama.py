"""Llama-family causal LM (flagship model; parity target: the reference's
llama/llama2 inference containers module_inject/containers/llama*.py and
inference/v2/model_implementations/llama_v2).

TPU-first design notes:
* bf16 compute, fp32 RMSNorm accumulations, einsum-heavy so every FLOP lands
  on the MXU;
* tensor parallel = Megatron-style column/row sharding stated as
  ``partition_rules`` (PartitionSpec over the 'model' mesh axis).  One-token
  decode, short sequences and every one-chip run are those rules under
  GSPMD and nothing else; a whole-sequence forward on a mesh whose 'model'
  axis is larger than 1, with chunks of at least 512 tokens a rank, takes
  the rules' collectives as ring steps under the GEMMs
  (:mod:`deepspeed_tpu.parallel.tensor_overlap`: ``LlamaModel`` asks its
  ``plan``; the residual stream, its adds and its norms are then
  token-sharded over 'model' between a row-parallel GEMM and the next
  column-parallel one).  Same parameters, same rules, same numbers;
* sequence parallel (Ulysses) = optional all-to-all head<->seq re-partition
  around attention via :mod:`deepspeed_tpu.sequence` when the mesh has a
  'seq' axis;
* rotary embeddings computed in fp32 and applied in compute dtype.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from deepspeed_tpu.ops.attention import dot_product_attention
from deepspeed_tpu.parallel import tensor_overlap


@dataclasses.dataclass
class LlamaConfig:
    vocab_size: int = 32000
    hidden_size: int = 4096
    intermediate_size: int = 11008
    num_hidden_layers: int = 32
    num_attention_heads: int = 32
    num_key_value_heads: int = 32
    max_position_embeddings: int = 4096
    rms_norm_eps: float = 1e-5
    rope_theta: float = 10000.0
    tie_word_embeddings: bool = False
    dtype: Any = jnp.bfloat16
    remat: bool = False  # activation checkpointing per layer
    # Explicitly fused projections (role of the reference's qkv_gemm/
    # mlp_gemm fused CUDA kernels, csrc/transformer/inference
    # pt_binding.cpp:1943). Off by default: XLA already merges parallel
    # same-LHS dots, and the manual fuse+split measured ~4% SLOWER on v5e
    # (96.6 vs 92.6 ms/step on the 125M bench) — kept as an option for
    # layouts where the automatic merge misses.
    fused_qkv: bool = False
    fused_gate_up: bool = False
    # Mistral-style sliding-window attention: each token attends to at
    # most the previous `sliding_window` positions (None = full causal).
    sliding_window: Any = None
    # OLMo-2 / OLMoE: RMSNorm over the WHOLE q and k projections (all heads
    # at once), before the head split and the rotary embedding.
    qk_norm: bool = False

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @staticmethod
    def tiny(**kw) -> "LlamaConfig":
        base = dict(vocab_size=256, hidden_size=64, intermediate_size=128,
                    num_hidden_layers=2, num_attention_heads=4,
                    num_key_value_heads=2, max_position_embeddings=128)
        base.update(kw)
        return LlamaConfig(**base)

    @staticmethod
    def llama2_7b(**kw) -> "LlamaConfig":
        return LlamaConfig(**kw)

    @staticmethod
    def llama2_70b(**kw) -> "LlamaConfig":
        base = dict(hidden_size=8192, intermediate_size=28672,
                    num_hidden_layers=80, num_attention_heads=64,
                    num_key_value_heads=8)
        base.update(kw)
        return LlamaConfig(**base)


# Megatron-style TP sharding over the 'model' axis: attention QKV + MLP
# up/gate are column-parallel, attention out + MLP down row-parallel,
# embedding/LM-head vocab-parallel (reference module_inject/auto_tp.py row/col
# policy; inference/v2/model_implementations/sharding/).
LLAMA_PARTITION_RULES = [
    (r"embed_tokens/embedding", P("model", None)),
    (r"(q_proj|k_proj|v_proj|qkv_proj)/kernel", P(None, "model")),
    (r"o_proj/kernel", P("model", None)),
    (r"(gate_proj|up_proj|gate_up_proj)/kernel", P(None, "model")),
    (r"down_proj/kernel", P("model", None)),
    (r"lm_head/kernel", P(None, "model")),
    (r".*norm.*", P()),
]


class RMSNorm(nn.Module):
    eps: float = 1e-5

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],))
        x32 = x.astype(jnp.float32)
        var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
        y = x32 * jax.lax.rsqrt(var + self.eps)
        return (y * scale.astype(jnp.float32)).astype(x.dtype)


def rotary_embedding(positions: jax.Array, head_dim: int, theta: float):
    """positions: [B,S] int32 -> (cos, sin): [B,S,1,D/2] fp32."""
    inv_freq = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                                / head_dim))
    angles = positions[..., None].astype(jnp.float32) * inv_freq  # [B,S,D/2]
    return jnp.cos(angles)[:, :, None, :], jnp.sin(angles)[:, :, None, :]


def apply_rotary(x, cos, sin):
    """x: [B,S,H,D]; rotate-half formulation (fp32 math)."""
    x32 = x.astype(jnp.float32)
    x1, x2 = jnp.split(x32, 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def init_kv_cache(config: LlamaConfig, batch: int, max_len: int):
    """Per-layer KV cache pytree for incremental decoding (the role of the
    reference's inference KV buffers, ops/transformer/inference)."""
    shape = (batch, max_len, config.num_key_value_heads, config.head_dim)
    return {
        f"layers_{i}": {"k": jnp.zeros(shape, config.dtype),
                        "v": jnp.zeros(shape, config.dtype)}
        for i in range(config.num_hidden_layers)
    }


class _DenseKernel(nn.Module):
    """The ``kernel`` of a bias-free ``nn.Dense`` of the same name, in the
    compute dtype and without the product: what a ring site multiplies by.
    Only ever applied (``LlamaModel`` plans no ring while it initialises),
    so ``nn.Dense`` stays the one place that creates the parameter."""
    features: int
    dtype: Any

    @nn.compact
    def __call__(self, in_features: int):
        return self.param("kernel", nn.linear.default_kernel_init,
                          (in_features, self.features),
                          jnp.float32).astype(self.dtype)


def _kernel(cfg: LlamaConfig, features: int, name: str, fan_in: int):
    return _DenseKernel(features, cfg.dtype, name=name)(fan_in)


class LlamaAttention(nn.Module):
    """``ring`` (a ``tensor_overlap.Ring``, from ``LlamaBlock``): ``x``
    arrives token-sharded over 'model' and so does the result; q/k/v are
    the ring gather's products and ``o_proj`` the ring scatter's."""
    config: LlamaConfig
    ring: Any = None

    @nn.compact
    def __call__(self, x, positions, attention_fn=None, cache=None,
                 cache_index=None):
        cfg = self.config
        ring = self.ring
        h, hkv, d = (cfg.num_attention_heads, cfg.num_key_value_heads,
                     cfg.head_dim)
        dense = lambda feats, name: nn.Dense(
            feats, use_bias=False, dtype=cfg.dtype,
            param_dtype=jnp.float32, name=name)
        if ring:
            q, k, v = tensor_overlap.gather_column_parallel(
                ring, x.astype(cfg.dtype),
                {name: _kernel(cfg, nh * d, name, x.shape[-1]) for name, nh
                 in (("q_proj", h), ("k_proj", hkv), ("v_proj", hkv))}
            ).values()
        elif cfg.fused_qkv:
            # one wide matmul (fused qkv_gemm) then split
            qkv = dense((h + 2 * hkv) * d, "qkv_proj")(x)
            q, k, v = jnp.split(qkv, [h * d, (h + hkv) * d], axis=-1)
        else:
            q = k = v = None

        def heads(t, name, n):
            """One projection (unless the fused one made it), its RMSNorm
            over all heads at once where the config has ``qk_norm``, the
            head split."""
            if t is None:
                t = dense(n * d, f"{name}_proj")(x)
            if cfg.qk_norm and name != "v":
                t = RMSNorm(cfg.rms_norm_eps, name=f"{name}_norm")(t)
            return t.reshape(*x.shape[:2], n, d)

        q, k, v = heads(q, "q", h), heads(k, "k", hkv), heads(v, "v", hkv)
        cos, sin = rotary_embedding(positions, d, cfg.rope_theta)
        q = apply_rotary(q, cos, sin)
        k = apply_rotary(k, cos, sin)
        attn = attention_fn or dot_product_attention

        def prefill_attn(q_, k_, v_):
            # Mistral SWA: the window is a first-class kernel argument
            # (flash path skips out-of-band k-blocks; no dense mask)
            if cfg.sliding_window is not None and \
                    q_.shape[1] > cfg.sliding_window:
                return attn(q_, k_, v_, causal=True,
                            window=cfg.sliding_window)
            return attn(q_, k_, v_, causal=True)

        if cache is None:
            out = prefill_attn(q, k, v)
            new_cache = None
        else:
            # write the new keys/values at cache_index
            ck = jax.lax.dynamic_update_slice(
                cache["k"], k.astype(cache["k"].dtype), (0, cache_index, 0, 0))
            cv = jax.lax.dynamic_update_slice(
                cache["v"], v.astype(cache["v"].dtype), (0, cache_index, 0, 0))
            new_cache = {"k": ck, "v": cv}
            if x.shape[1] > 1 and isinstance(cache_index, int) \
                    and cache_index == 0:
                # prefill from an empty cache: causal attention over the
                # fresh k/v — flash-kernel eligible (window included)
                out = prefill_attn(q, k, v)
            else:
                # incremental decode: attend over the cache with a validity
                # mask (key_pos <= query_pos)
                max_len = ck.shape[1]
                key_pos = jnp.arange(max_len, dtype=jnp.int32)
                mask = key_pos[None, None, None, :] <= \
                    positions[:, None, :, None]
                if cfg.sliding_window is not None:
                    mask = mask & (key_pos[None, None, None, :] >
                                   positions[:, None, :, None] -
                                   cfg.sliding_window)
                out = attn(q, ck, cv, causal=False, mask=mask)
        out = out.reshape(*x.shape[:2], h * d)
        if ring:
            return tensor_overlap.row_parallel_scatter(
                ring, out, _kernel(cfg, cfg.hidden_size, "o_proj", h * d),
                name="o_proj"), new_cache
        return dense(cfg.hidden_size, "o_proj")(out), new_cache


class LlamaMLP(nn.Module):
    """``ring``: as :class:`LlamaAttention`; gate / up are a ring gather's
    products and ``down_proj`` a ring scatter's (``gated_mlp``)."""
    config: LlamaConfig
    ring: Any = None

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        ring = self.ring
        dense = lambda feats, name: nn.Dense(
            feats, use_bias=False, dtype=cfg.dtype,
            param_dtype=jnp.float32, name=name)
        if ring:
            inter, hidden = cfg.intermediate_size, cfg.hidden_size
            return tensor_overlap.gated_mlp(
                ring, x.astype(cfg.dtype),
                _kernel(cfg, inter, "gate_proj", hidden),
                _kernel(cfg, inter, "up_proj", hidden),
                _kernel(cfg, hidden, "down_proj", inter), nn.silu)
        if cfg.fused_gate_up:
            # one wide matmul (fused mlp_gemm) then split
            gu = dense(2 * cfg.intermediate_size, "gate_up_proj")(x)
            gate, up = jnp.split(gu, 2, axis=-1)
        else:
            gate = dense(cfg.intermediate_size, "gate_proj")(x)
            up = dense(cfg.intermediate_size, "up_proj")(x)
        return dense(cfg.hidden_size, "down_proj")(nn.silu(gate) * up)


class LlamaBlock(nn.Module):
    """``ring``: ``x`` is token-sharded over 'model' through the block (a
    rank holds ``[B, T/n, H]``): both norms and both residual adds run on
    a rank's chunk."""
    config: LlamaConfig
    ring: Any = None

    @nn.compact
    def __call__(self, x, positions, attention_fn=None, cache=None,
                 cache_index=None):
        cfg = self.config
        a, new_cache = LlamaAttention(cfg, self.ring, name="self_attn")(
            RMSNorm(cfg.rms_norm_eps, name="input_layernorm")(x),
            positions, attention_fn, cache, cache_index)
        x = x + a
        m = LlamaMLP(cfg, self.ring, name="mlp")(
            RMSNorm(cfg.rms_norm_eps, name="post_attention_layernorm")(x))
        return x + m, new_cache


class LlamaModel(nn.Module):
    config: LlamaConfig
    attention_fn: Any = None

    @nn.compact
    def __call__(self, input_ids, tie_logits: bool = False, positions=None,
                 cache=None, cache_index=None):
        cfg = self.config
        b, s = input_ids.shape
        if positions is None:
            positions = jnp.broadcast_to(
                jnp.arange(s, dtype=jnp.int32)[None], (b, s))
        embed = nn.Embed(cfg.vocab_size, cfg.hidden_size,
                         dtype=cfg.dtype, param_dtype=jnp.float32,
                         name="embed_tokens")
        x = embed(input_ids)
        # four GEMM sites a layer: q/k/v, o_proj, gate/up, down_proj
        ring = None if self.is_initializing() or cfg.fused_qkv or \
            cfg.fused_gate_up else tensor_overlap.plan(
                s, sites=4 * cfg.num_hidden_layers, cache=cache,
                features=(cfg.num_attention_heads, cfg.num_key_value_heads,
                          cfg.intermediate_size))
        if ring:
            # the embedding's all-reduce becomes a reduce-scatter
            x = ring.shard_tokens(x)
        block = LlamaBlock
        if cfg.remat and cache is None:
            block = nn.remat(
                LlamaBlock,
                policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
        new_cache = {} if cache is not None else None
        for i in range(cfg.num_hidden_layers):
            name = f"layers_{i}"
            layer_cache = cache[name] if cache is not None else None
            x, c = block(cfg, ring, name=name)(
                x, positions, self.attention_fn, layer_cache, cache_index)
            if cache is not None:
                new_cache[name] = c
        x = RMSNorm(cfg.rms_norm_eps, name="norm")(x)
        if ring:
            # the head is vocab-parallel: it takes the tokens whole
            x = ring.gather_tokens(x)
        if tie_logits:
            x = embed.attend(x.astype(cfg.dtype))
        return (x, new_cache) if cache is not None else x


class LlamaForCausalLM(nn.Module):
    """Returns loss when labels given (train contract), else logits.
    With ``cache`` (see :func:`init_kv_cache`) runs incremental decoding and
    returns ``(logits, new_cache)``."""

    config: LlamaConfig
    attention_fn: Any = None

    # TP rules the engine picks up automatically
    @property
    def partition_rules(self):
        return LLAMA_PARTITION_RULES

    @nn.compact
    def __call__(self, input_ids, labels=None, positions=None, cache=None,
                 cache_index=None):
        cfg = self.config
        out = LlamaModel(cfg, self.attention_fn, name="model")(
            input_ids, tie_logits=cfg.tie_word_embeddings,
            positions=positions, cache=cache, cache_index=cache_index)
        x, new_cache = out if cache is not None else (out, None)

        def head(x):
            if cfg.tie_word_embeddings:
                return x
            return nn.Dense(cfg.vocab_size, use_bias=False, dtype=cfg.dtype,
                            param_dtype=jnp.float32, name="lm_head")(x)

        if labels is not None:
            # one device scope over the head and the loss, forward and
            # backward: the training step's vocabulary-sized work
            with jax.named_scope("lm_head_loss"):
                return cross_entropy_loss(head(x), labels)
        logits = head(x)
        return (logits, new_cache) if cache is not None else logits


def cross_entropy_loss(logits, labels, ignore_index: int = -100):
    """Next-token CE in fp32 with ignore-index masking."""
    logits = logits[:, :-1].astype(jnp.float32)
    targets = labels[:, 1:]
    mask = (targets != ignore_index)
    safe_targets = jnp.where(mask, targets, 0)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, safe_targets[..., None],
                               axis=-1).squeeze(-1)
    nll = (logz - gold) * mask
    return jnp.sum(nll) / jnp.maximum(jnp.sum(mask), 1)
