"""Ragged (paged-KV) Mixtral / OLMoE forward for the FastGen engine.

Reference analog: ``inference/v2/model_implementations/mixtral/`` served by
the MoE ragged kernels (``kernels/ragged_ops/{top_k_gating,moe_scatter,
moe_gather}/``, ``kernels/cutlass_ops/moe_gemm/``).

TPU-native design: the attention/paged-KV machinery is shared with
:class:`RaggedLlama` (same flat token buffer, same blocked-flash kernels,
same two-segment batches; q/k RMSNorm where the layer's parameters carry
``q_norm``/``k_norm``, as OLMoE's do); the FFN is a **dropless** top-k
routed MoE over the flat ``[T, H]`` buffer:

* ``moe/router``: post-attention norm, router logits in float32, softmax
  over ALL experts, top-k; the weights renormalised (HF Mixtral) or kept
  as the softmax gave them (``config.norm_topk_prob`` false: OLMoE) — the
  reference's ★top_k_gating kernel;
* ``moe/dispatch``: counting sort of the ``T x k`` routed rows by expert
  and the gather of their activations (★moe_scatter);
* ``moe/experts``: three calls of the grouped GEMM Mosaic kernel
  (``ops/grouped_gemm.py::_gmm_kernel``, ★moe_gemm) around the SwiGLU
  product: each expert multiplies only the rows routed to it, so FLOPs
  scale with ``k x T``, not ``E x T``.  This is the DEFAULT path, on the
  TPU and (as the XLA composition ``gmm_reference``) off it;
* ``moe/combine``: unsort and the weighted sum of each token's ``k`` rows
  (★moe_gather).

``dropless_moe(..., grouped=False)`` computes every expert over every
token by dense einsums and masks: ``E / k`` times the FLOPs (8x for OLMoE).
It is the parity oracle of the tests and of ``chip_smoke.py`` and is never
served.

Dropless gating is what makes MoE *ragged-safe*: with no capacity buckets
there is no cross-token interaction, so the pad lanes of the token budget
cannot perturb real tokens' routing — the property capacity-based gating
(runtime/moe/sharded_moe.py top2gating) does not have.

The param tree is EXACTLY :class:`models.mixtral.MixtralForCausalLM`'s, so
training checkpoints serve directly.
"""

from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

from deepspeed_tpu.inference.v2.model_implementations.ragged_llama import (
    _rms_norm,
    _rotary,
    ragged_attention_block,
)
from deepspeed_tpu.models.mixtral import MixtralConfig


def moe_router(x, wg, k: int, renormalize: bool = True, bias=None,
               routed_scale: float = 1.0, norm_eps=None):
    """Router of the dropless MoE: ``x`` [T, H] (normed) x ``wg`` [H, E] in
    float32 -> (topi [T, k] int32, weights [T, k] float32).  Float32
    products as well as sums: on a TPU a float32 matmul at the default
    precision rounds its operands to bf16, which changes nothing for a bf16
    engine (its activations and weights are bf16 values already) and flips
    routings on near ties for a float32 one.  Softmax then top-k; with a
    selection ``bias`` [E] (static: the router's parameters carry one) the
    sigmoid router of the DeepSeek-V3 family, its weights times
    ``routed_scale``; ``norm_eps`` (static) replaces the constant its
    renormalisation adds to the sum where a family's published code has
    another (LFM2: 1e-6)."""
    from deepspeed_tpu.ops.grouped_gemm import (exact_topk_routing,
                                                sigmoid_bias_topk_routing)

    with jax.named_scope("moe/router"):
        logits = jnp.matmul(x.astype(jnp.float32), wg.astype(jnp.float32),
                            precision=jax.lax.Precision.HIGHEST)  # [T, E]
        if bias is not None:
            kwargs = {} if norm_eps is None else {"norm_eps": norm_eps}
            return sigmoid_bias_topk_routing(logits, bias, k, renormalize,
                                             routed_scale, **kwargs)
        return exact_topk_routing(logits, k, renormalize)


def dropless_moe(x, moe_params, k: int, dtype, grouped=None,
                 renormalize: bool = True, expert_start: int = 0,
                 routed_scale: float = 1.0, norm_eps=None):
    """Dropless top-k MoE over a flat token buffer.

    x: [T, H]; returns [T, H]. Router math in fp32 (reference TopKGate is
    fp32, sharded_moe.py:348); expert compute in ``dtype``.

    The expert FFN runs through the grouped GEMM kernel
    (ops/grouped_gemm.py — the reference's ★moe_gemm/★moe_scatter/
    ★moe_gather pipeline): tokens are sorted by expert and each expert
    multiplies only its own row block, so FLOPs scale with k·T instead
    of E·T (4× fewer for Mixtral's 8-expert top-2, 8x for OLMoE's 64 at
    top-8).  ``grouped=False`` forces the dense all-experts einsum (the
    parity oracle).  ``renormalize`` (static) is HF ``norm_topk_prob``.

    A share of the experts (static, read from the shapes: the expert
    matrices hold fewer experts than the router has outputs): the router
    still scores every expert and takes the top-k of all of them, and the
    result is the part of the sum that the held experts
    ``[expert_start, expert_start + held)`` give; a token routed wholly
    elsewhere gets zeros.  With every expert held this is the path above,
    unchanged.  A shared expert (``shared_expert`` in ``moe_params``; gated
    where ``shared_expert_gate`` is there too) is added for every token.
    A router whose parameters carry ``e_score_correction_bias`` is the
    sigmoid router with a selection bias, its weights times
    ``routed_scale`` (static), renormalised with ``norm_eps`` (static; None:
    the router's own constant).
    """
    from deepspeed_tpu.ops.grouped_gemm import grouped_moe_ffn

    wg = moe_params["gate"]["wg"]["kernel"]            # [H, E]
    experts = moe_params["experts"]
    topi, w = moe_router(
        x, wg, k, renormalize,
        bias=moe_params["gate"].get("e_score_correction_bias"),
        routed_scale=routed_scale, norm_eps=norm_eps)  # [T, k]
    e_count = wg.shape[1]
    w_gate = experts["w_gate"].astype(dtype)           # [E, H, F]
    w_up = experts["w_up"].astype(dtype)
    w_down = experts["w_down"].astype(dtype)
    # a share and a shared expert exist on the grouped path only (the
    # dense composition below is the all-experts parity oracle)
    share = w_gate.shape[0] != e_count
    shared = "shared_expert" in moe_params
    if share or shared or grouped is None or grouped:
        kwargs = {"expert_start": int(expert_start)} if share else {}
        out = grouped_moe_ffn(x.astype(dtype), topi, w.astype(dtype),
                              w_gate, w_up, w_down, **kwargs)
        if shared:
            out = out + _shared_expert(x.astype(dtype), moe_params, dtype)
        return out
    # dense all-experts composition (reference/oracle path)
    comb = jnp.sum(jax.nn.one_hot(topi, e_count, dtype=jnp.float32)
                   * w[..., None], axis=1)             # [T, E]
    xe = x.astype(dtype)
    h = jax.nn.silu(jnp.einsum("tm,emf->etf", xe, w_gate)) * \
        jnp.einsum("tm,emf->etf", xe, w_up)            # [E, T, F]
    out = jnp.einsum("etf,efm->etm", h, w_down)        # [E, T, H]
    return jnp.einsum("te,etm->tm", comb.astype(dtype), out)


def _shared_expert(x, moe_params, dtype):
    """The expert every token takes (device scope ``moe/shared``):
    ``down(silu(gate x) * up x)``, times ``sigmoid(x . w_sg)`` where the
    parameters carry that gate (static)."""
    with jax.named_scope("moe/shared"):
        se = moe_params["shared_expert"]
        hmid = jax.nn.silu(x @ se["gate_proj"]["kernel"].astype(dtype)) \
            * (x @ se["up_proj"]["kernel"].astype(dtype))
        y = hmid @ se["down_proj"]["kernel"].astype(dtype)
        if "shared_expert_gate" not in moe_params:
            return y
        sg = jax.nn.sigmoid(
            x.astype(jnp.float32)
            @ moe_params["shared_expert_gate"]["kernel"].astype(jnp.float32))
        return (sg * y.astype(jnp.float32)).astype(dtype)


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
