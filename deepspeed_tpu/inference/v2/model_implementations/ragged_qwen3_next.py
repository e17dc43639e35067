"""Ragged Qwen3-Next forward for the FastGen engine (``model_type:
qwen3_next``): three Gated DeltaNet (linear-attention) layers to every
gated softmax-attention layer, a routed-expert FFN with a shared expert in
every layer.

What is new beside :class:`RaggedLlama` / :class:`RaggedMixtral`:

* **Two kinds of per-sequence state.**  An attention layer keeps keys and
  values in the paged pool, as everywhere.  A Gated DeltaNet layer keeps,
  per sequence, a recurrent matrix per value head (float32) and the last
  ``conv_kernel - 1`` inputs of its causal convolution (flat in one row,
  ``modules/conv.py``), in a SLOT of the
  state manager's pool (``ragged/state_pool.py``; ``state_spec`` below is
  how the engine learns of it).  The cache tree handed to the step program
  has ``{k, v}`` for attention layers and ``{state, conv}`` for the others.
* **A chunk depends on the chunk before it.**  The batch carries each batch
  slot's state slot and the row its chunk starts at; a chunk whose first
  position is 0 starts from a zeroed state, on the device; pad rows (a
  tile's tail, the pad rows of a decode step) write the scratch slot or
  nothing, so no live slot changes.  Prompt chunks run through whole tiles
  (the engine's two-segment batches) and the chunked rule
  (``ops/gated_delta_rule.py::gdn_chunk``), single-token rows through the
  one-token update (``gdn_step``).
* **Gated attention** through the shared ``ragged_attention_block``: head
  size 256, RMSNorm per head on q and k with a zero-centred weight, rotary
  on the first quarter of each head, the output multiplied by the sigmoid
  of a gate that ``q_proj`` emits.
* **A share of the experts.**  The router scores all ``num_experts``; the
  layer holds ``held_experts`` of them from ``expert_start`` and computes
  their part of the sum (``dropless_moe``), plus the shared expert.  Nothing
  stands in for the chips that hold the rest.

Layout of the DeltaNet projections (what a checkpoint loader has to
produce; ``checkpoint/hf_loader.py`` regroups the published interleaving):
``in_proj_qkvz`` columns are ``q | k | v | z`` (all heads of q, then of k,
...), ``in_proj_ba`` columns ``b | a``, ``conv1d/kernel`` is ``[taps,
channels]`` over ``q | k | v`` with the LAST tap on the current token.
The mixer is ``modules/gdn.py::gdn_mixer`` (Olmo-Hybrid's too); the norm
before it is this file's.  Device scopes under ``layers_<i>``:
``gdn/in_proj`` (norm and both projections), ``gdn/conv``, ``gdn/rule``,
``gdn/out`` (gated norm and ``out_proj``); ``attn/*`` as RaggedLlama; ``moe/router``, ``moe/dispatch``,
``moe/experts``, ``moe/combine``, ``moe/shared``.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from deepspeed_tpu.inference.v2.modules.attention import (
    _rms_norm_1p,
    _rotary,
    ragged_attention_block,
)
from deepspeed_tpu.inference.v2.modules.gdn import (
    gdn_conv_dim,
    gdn_mixer,
    gdn_param_shapes,
    gdn_state_leaves,
)
from deepspeed_tpu.inference.v2.modules.moe import dropless_moe
from deepspeed_tpu.inference.v2.ragged.kv_cache import CacheLayoutError


@dataclasses.dataclass
class Qwen3NextConfig:
    vocab_size: int = 151936
    hidden_size: int = 2048
    num_hidden_layers: int = 48
    num_attention_heads: int = 16
    num_key_value_heads: int = 2
    head_dim: int = 256
    partial_rotary_factor: float = 0.25
    rope_theta: float = 1e7
    rms_norm_eps: float = 1e-6
    max_position_embeddings: int = 262144
    #: layer ``i`` is full attention when ``(i + 1) % interval == 0``
    full_attention_interval: int = 4
    linear_num_key_heads: int = 16
    linear_num_value_heads: int = 32
    linear_key_head_dim: int = 128
    linear_value_head_dim: int = 128
    linear_conv_kernel_dim: int = 4
    #: the router's width (every expert of the model)
    num_experts: int = 512
    num_experts_per_tok: int = 10
    moe_intermediate_size: int = 512
    shared_expert_intermediate_size: int = 512
    norm_topk_prob: bool = True
    #: the experts this program holds: ``[expert_start, expert_start +
    #: held_experts)`` of the router's; None = all of them
    held_experts: Optional[int] = None
    expert_start: int = 0
    dtype: Any = jnp.bfloat16
    # read by the shared attention block
    sliding_window: Optional[int] = None
    attn_output_gate: bool = True
    zero_centered_norm: bool = True

    def is_attention(self, i: int) -> bool:
        return (i + 1) % self.full_attention_interval == 0

    @property
    def conv_dim(self) -> int:
        return gdn_conv_dim(self)


def param_shapes(cfg: Qwen3NextConfig) -> Dict[str, Any]:
    """The parameter tree :class:`RaggedQwen3Next` reads, as shapes (every
    matrix stored [in, out])."""
    dt, h = cfg.dtype, cfg.hidden_size
    hq, hkv, d = cfg.num_attention_heads, cfg.num_key_value_heads, \
        cfg.head_dim
    e = cfg.held_experts or cfg.num_experts
    f, fs = cfg.moe_intermediate_size, cfg.shared_expert_intermediate_size
    sds = lambda *shape: jax.ShapeDtypeStruct(shape, dt)
    kern = lambda i, o: {"kernel": sds(i, o)}

    def layer(i):
        mixer = {"self_attn": {
            "q_proj": kern(h, 2 * hq * d), "k_proj": kern(h, hkv * d),
            "v_proj": kern(h, hkv * d), "o_proj": kern(hq * d, h),
            "q_norm": {"scale": sds(d)}, "k_norm": {"scale": sds(d)}}} \
            if cfg.is_attention(i) else {
                "linear_attn": gdn_param_shapes(cfg, sds)}
        return {
            "input_layernorm": {"scale": sds(h)},
            "post_attention_layernorm": {"scale": sds(h)},
            **mixer,
            "mlp": {
                "gate": {"wg": kern(h, cfg.num_experts)},
                "experts": {"w_gate": sds(e, h, f), "w_up": sds(e, h, f),
                            "w_down": sds(e, f, h)},
                "shared_expert": {"gate_proj": kern(h, fs),
                                  "up_proj": kern(h, fs),
                                  "down_proj": kern(fs, h)},
                "shared_expert_gate": kern(h, 1)}}

    return {"embed_tokens": {"embedding": sds(cfg.vocab_size, h)},
            **{f"layers_{i}": layer(i)
               for i in range(cfg.num_hidden_layers)},
            "norm": {"scale": sds(h)},
            "lm_head": kern(h, cfg.vocab_size)}


class RaggedQwen3Next:
    """Callable ragged forward bound to a :class:`Qwen3NextConfig`."""

    #: the cache tree is not one shape a layer; int8 KV is not wired
    supports_quantized_kv = False

    def __init__(self, config: Qwen3NextConfig, block_size: int):
        self.config = config
        self.block_size = block_size
        self.tp = 1
        #: None: each rule's Mosaic kernel on a TPU, its XLA composition
        #: elsewhere; tests pass True (the kernels in interpret mode)
        self.interpret: Optional[bool] = None

    @property
    def num_layers(self):
        return self.config.num_hidden_layers

    @property
    def num_kv_heads(self):
        return self.config.num_key_value_heads

    @property
    def head_dim(self):
        return self.config.head_dim

    @property
    def state_spec(self) -> Dict[str, Any]:
        """The per-sequence state the engine's slot pool holds for this
        model: which layers, and each leaf's per-slot shape and dtype."""
        cfg = self.config
        return {
            "layers": [i for i in range(cfg.num_hidden_layers)
                       if not cfg.is_attention(i)],
            "leaves": gdn_state_leaves(cfg)}

    def __call__(self, params: Dict[str, Any], cache: Dict[str, Any],
                 batch: Dict[str, jax.Array], prefill_tile=None,
                 decode=False):
        """Returns ``(logits [S, vocab], new cache)``.  ``batch`` carries
        ``state_slot`` and ``chunk_start`` beside the usual fields."""
        cfg = self.config
        dt = cfg.dtype
        if not decode and not prefill_tile:
            raise CacheLayoutError(
                "RaggedQwen3Next runs decode steps and two-segment (tiled) "
                "batches; a batch packed back to back has no tile a "
                "sequence's state could be carried along")
        with jax.named_scope("embed"):
            x = params["embed_tokens"]["embedding"].astype(dt)[
                batch["token_ids"]]
        h, hkv, d = (cfg.num_attention_heads, cfg.num_key_value_heads,
                     cfg.head_dim)
        cos, sin = _rotary(batch["token_pos"],
                           int(d * cfg.partial_rotary_factor), cfg.rope_theta)
        new_cache = {}
        for i in range(cfg.num_hidden_layers):
            lp = params[f"layers_{i}"]
            with jax.named_scope(f"layers_{i}"):
                if cfg.is_attention(i):
                    with jax.named_scope("attn/qkv"):
                        xa = _rms_norm_1p(x, lp["input_layernorm"]["scale"],
                                          cfg.rms_norm_eps)
                    out, new_cache[f"layer_{i}"] = ragged_attention_block(
                        lp["self_attn"], xa, cache[f"layer_{i}"], batch,
                        self.block_size, cfg, h, hkv, d, cos, sin,
                        prefill_tile=prefill_tile, decode_mode=decode)
                else:
                    with jax.named_scope("gdn/in_proj"):
                        xn = _rms_norm_1p(x, lp["input_layernorm"]["scale"],
                                          cfg.rms_norm_eps)
                    out, new_cache[f"layer_{i}"] = gdn_mixer(
                        lp["linear_attn"], xn, cache[f"layer_{i}"], batch,
                        prefill_tile, cfg, interpret=self.interpret)
                x = x + out
                with jax.named_scope("moe/router"):
                    xm = _rms_norm_1p(
                        x, lp["post_attention_layernorm"]["scale"],
                        cfg.rms_norm_eps)
                x = x + dropless_moe(
                    xm, lp["mlp"], cfg.num_experts_per_tok, dt,
                    renormalize=cfg.norm_topk_prob,
                    expert_start=cfg.expert_start)
        with jax.named_scope("lm_head"):
            x = _rms_norm_1p(x, params["norm"]["scale"], cfg.rms_norm_eps)
            x = x[batch["logits_idx"]]
            logits = x @ params["lm_head"]["kernel"].astype(dt)
        return logits, new_cache
