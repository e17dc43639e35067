"""Ragged (paged-KV) Mixtral / OLMoE forward for the FastGen engine.

Reference analog: ``inference/v2/model_implementations/mixtral/`` served by
the MoE ragged kernels (``kernels/ragged_ops/{top_k_gating,moe_scatter,
moe_gather}/``, ``kernels/cutlass_ops/moe_gemm/``).

TPU-native design: the attention/paged-KV machinery is shared with
:class:`RaggedLlama` (same flat token buffer, same blocked-flash kernels,
same two-segment batches; q/k RMSNorm where the layer's parameters carry
``q_norm``/``k_norm``, as OLMoE's do); the FFN is the **dropless** top-k
routed MoE of ``modules/moe.py`` (``moe/router``, ``moe/dispatch``,
``moe/experts``, ``moe/combine``), the weights renormalised (HF Mixtral) or
kept as the softmax gave them (``config.norm_topk_prob`` false: OLMoE).

The param tree is EXACTLY :class:`models.mixtral.MixtralForCausalLM`'s, so
training checkpoints serve directly.
"""

from __future__ import annotations

from typing import Any, Dict

import jax

from deepspeed_tpu.inference.v2.modules.attention import (
    _rms_norm,
    _rotary,
    ragged_attention_block,
)
from deepspeed_tpu.inference.v2.modules.moe import dropless_moe
from deepspeed_tpu.models.mixtral import MixtralConfig


class RaggedMixtral:
    """Callable ragged MoE forward bound to a :class:`MixtralConfig`
    (Mixtral, and OLMoE through ``qk_norm`` / ``norm_topk_prob``)."""

    #: attention goes through the shared ragged_attention_block, whose
    #: write path quantizes on insert — int8 KV works here too
    supports_quantized_kv = True

    #: the grouped GEMM path.  chip_smoke.py's parity oracle is a subclass
    #: that sets this False (dense all-experts einsum); never served
    grouped = None

    def __init__(self, config: MixtralConfig, block_size: int):
        self.config = config
        self.block_size = block_size
        self.tp = 1  # MoE TP serving composes via the 'expert' axis later

    @property
    def num_layers(self):
        return self.config.num_hidden_layers

    @property
    def num_kv_heads(self):
        return self.config.num_key_value_heads

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

        with jax.named_scope("embed"):
            x = params["embed_tokens"]["embedding"].astype(dt)[token_ids]
        h, hkv, d = (cfg.num_attention_heads, cfg.num_key_value_heads,
                     cfg.head_dim)
        cos, sin = _rotary(token_pos, d, cfg.rope_theta)
        new_cache = {}
        # device scopes as RaggedLlama's (layers_<i>/attn/qkv with its
        # norm, attn/rope_insert, the attention read, attn/out_proj), then
        # moe/router (with its norm), moe/dispatch, moe/experts,
        # moe/combine in place of mlp, then lm_head
        for i in range(cfg.num_hidden_layers):
            lp = params[f"layers_{i}"]
            with jax.named_scope(f"layers_{i}"):
                with jax.named_scope("attn/qkv"):
                    xa = _rms_norm(x, lp["input_layernorm"]["scale"],
                                   cfg.rms_norm_eps)
                out, new_cache[f"layer_{i}"] = ragged_attention_block(
                    lp["self_attn"], xa, kv_cache[f"layer_{i}"], batch,
                    self.block_size, cfg, h, hkv, d, cos, sin,
                    prefill_tile=prefill_tile, decode_mode=decode)
                x = x + out
                with jax.named_scope("moe/router"):
                    xm = _rms_norm(x, lp["post_attention_layernorm"]["scale"],
                                   cfg.rms_norm_eps)
                x = x + dropless_moe(
                    xm, lp["block_sparse_moe"]["deepspeed_moe"],
                    cfg.num_experts_per_tok, dt, grouped=self.grouped,
                    renormalize=cfg.norm_topk_prob)
        with jax.named_scope("lm_head"):
            x = _rms_norm(x, params["norm"]["scale"], cfg.rms_norm_eps)
            # slot rows gathered BEFORE the vocab matmul (prefill buckets
            # would otherwise unembed every packed token row)
            x = x[batch["logits_idx"]]
            logits = x @ params["lm_head"]["kernel"].astype(dt)
        return logits, new_cache
