"""Ragged LFM2-MoE forward for the FastGen engine (``model_type:
lfm2_moe``): a gated short convolution in three layers of four, grouped-query
softmax attention with 64-wide heads in the fourth, a dense SwiGLU in the
first ``num_dense_layers`` layers and a routed-expert FFN (sigmoid scores, a
selection bias, no shared expert) in the rest, the head tied to the
embedding.

What is new beside :class:`RaggedQwen3Next`, whose call signature and slot
pool this model shares:

* **The gated short convolution** (``conv_L_cache`` K = 3): ``[B | C | u] =
  norm(x) W_in``; ``g = B * u``; ``c_t = sum_j w[j] * g_{t-K+1+j}`` depthwise
  over the hidden channels, the last tap on the current token, zeros before
  position 0, NO activation; ``y = (C * c) W_out``.  Per sequence a layer
  keeps the last ``K - 1`` rows of ``g`` in a slot of the state manager's
  pool (``ragged/state_pool.py``): ``state_spec`` has the one leaf ``conv``,
  the ``K - 1`` rows flat in one row ``[(K - 1) hidden]``.
  (The published cache keeps K columns; the oldest is never read again.)
  The convolution over a ragged batch, its tail carried across chunk
  boundaries, through decode steps and past pad rows, is
  ``modules/conv.py::_causal_conv`` with no activation (one-token rows
  through its one-hot form, the tile segment through its chunk form).
* **A flat pool row.**  Heads of 64 are half a lane tile: the attention
  layers' pools are ``[rows, Hkv*D]`` (512 lanes at the published widths,
  whole tiles), the form ``BlockedKVCache`` stores every float pool of
  whole-tile rows in (``kv_cache.flat_row``; until PR 41 this model asked
  for it by a ``kv_row`` of its own), which the decode walk and the tiled
  prefill kernel read as stored (``kernels/blocked_flash.py``, the note on
  the walk's arithmetic: two heads of 64 a lane tile).  q and
  k take an RMSNorm per head (plain weights) before the rotation, through
  the shared ``ragged_attention_block``.
* **Every expert held**: the router of the DeepSeek-V3 family
  (``sigmoid_bias_topk_routing``) with the published code's ``1e-6`` in the
  renormalisation (``router_norm_eps``; ``config.json`` does not carry it).

Static branches are on a layer's own parameters: one with ``conv`` is a
convolution layer, one whose ``mlp`` has a ``gate`` is routed.  Decode steps
and two-segment (tiled) batches only, as every model with state slots.

Layout (what ``checkpoint/hf_loader.py`` produces): every matrix [in, out];
``conv/in_proj`` columns ``B | C | u``; ``conv/conv1d/kernel`` [taps,
channels]; experts stacked ``w_gate`` (w1) / ``w_up`` (w3) ``[E, H, F]``,
``w_down`` (w2) ``[E, F, H]``; the selection bias ``mlp/gate/
e_score_correction_bias`` (published ``expert_bias``).  Device scopes under
``layers_<i>``: ``conv/in_proj`` (norm and ``W_in``), ``conv/mix`` (``B *
u``, the taps with the slot's tail, the new tail, ``C *``), ``conv/out_proj``;
``attn/*`` as RaggedLlama; ``mlp``; ``moe/router``, ``moe/dispatch``,
``moe/experts``, ``moe/combine``.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Sequence

import jax
import jax.numpy as jnp

from deepspeed_tpu.inference.v2.modules.attention import (
    _rms_norm,
    _rotary,
    ragged_attention_block,
)
from deepspeed_tpu.inference.v2.modules.conv import _causal_conv
from deepspeed_tpu.inference.v2.modules.moe import dropless_moe
from deepspeed_tpu.inference.v2.ragged.kv_cache import CacheLayoutError
from deepspeed_tpu.ops.quantized_matmul import qmm

F32 = jnp.float32


@dataclasses.dataclass
class Lfm2Config:
    vocab_size: int = 65536
    hidden_size: int = 2048
    #: the dense SwiGLU of the first ``num_dense_layers`` layers
    intermediate_size: int = 11776
    moe_intermediate_size: int = 1536
    num_hidden_layers: int = 40
    #: "conv" | "full_attention" a layer; None: the published pattern,
    #: attention in layers 2, 6, 10, ...
    layer_types: Optional[Sequence[str]] = None
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    #: ``hidden_size // num_attention_heads`` when the config has no key
    head_dim: Optional[int] = None
    conv_L_cache: int = 3
    conv_bias: bool = False
    num_dense_layers: int = 2
    num_experts: int = 64
    num_experts_per_tok: int = 4
    norm_topk_prob: bool = True
    use_expert_bias: bool = True
    routed_scaling_factor: float = 1.0
    #: what the published code adds to the sum it renormalises by
    router_norm_eps: float = 1e-6
    norm_eps: float = 1e-5
    rope_theta: float = 1e6
    max_position_embeddings: int = 128000
    tie_embedding: bool = True
    dtype: Any = jnp.bfloat16
    # read by the shared attention block
    sliding_window: Optional[int] = None

    def __post_init__(self):
        if self.head_dim is None:
            self.head_dim = self.hidden_size // self.num_attention_heads
        if self.layer_types is None:
            self.layer_types = ["full_attention" if i % 4 == 2 else "conv"
                                for i in range(self.num_hidden_layers)]
        self.layer_types = list(self.layer_types)
        if len(self.layer_types) != self.num_hidden_layers or set(
                self.layer_types) - {"conv", "full_attention"}:
            raise ValueError(
                f"layer_types: {self.num_hidden_layers} entries of 'conv' | "
                f"'full_attention' wanted, got {self.layer_types}")
        if self.conv_bias or not self.use_expert_bias:
            raise NotImplementedError(
                f"conv_bias={self.conv_bias}, use_expert_bias="
                f"{self.use_expert_bias}: the convolution has no bias and "
                f"the router is the sigmoid one with a selection bias")

    @property
    def rms_norm_eps(self) -> float:        # the attention block's name
        return self.norm_eps

    def is_attention(self, i: int) -> bool:
        return self.layer_types[i] == "full_attention"

    def is_moe(self, i: int) -> bool:
        return i >= self.num_dense_layers


def param_shapes(cfg: Lfm2Config) -> Dict[str, Any]:
    """The parameter tree :class:`RaggedLfm2` reads, as shapes."""
    dt, h = cfg.dtype, cfg.hidden_size
    hq, hkv, d = cfg.num_attention_heads, cfg.num_key_value_heads, \
        cfg.head_dim
    e, f = cfg.num_experts, cfg.moe_intermediate_size
    sds = lambda *shape: jax.ShapeDtypeStruct(shape, dt)
    kern = lambda i, o: {"kernel": sds(i, o)}

    def layer(i):
        mixer = {"self_attn": {
            "q_proj": kern(h, hq * d), "k_proj": kern(h, hkv * d),
            "v_proj": kern(h, hkv * d), "o_proj": kern(hq * d, h),
            "q_norm": {"scale": sds(d)}, "k_norm": {"scale": sds(d)}}} \
            if cfg.is_attention(i) else {"conv": {
                "in_proj": kern(h, 3 * h),
                "conv1d": {"kernel": sds(cfg.conv_L_cache, h)},
                "out_proj": kern(h, h)}}
        mlp = {"gate": {"wg": kern(h, e),
                        "e_score_correction_bias": sds(e)},
               "experts": {"w_gate": sds(e, h, f), "w_up": sds(e, h, f),
                           "w_down": sds(e, f, h)}} if cfg.is_moe(i) else {
            "gate_proj": kern(h, cfg.intermediate_size),
            "up_proj": kern(h, cfg.intermediate_size),
            "down_proj": kern(cfg.intermediate_size, h)}
        return {"operator_norm": {"scale": sds(h)},
                "ffn_norm": {"scale": sds(h)}, **mixer, "mlp": mlp}

    tree = {"embed_tokens": {"embedding": sds(cfg.vocab_size, h)},
            **{f"layers_{i}": layer(i)
               for i in range(cfg.num_hidden_layers)},
            "norm": {"scale": sds(h)}}
    if not cfg.tie_embedding:
        tree["lm_head"] = kern(h, cfg.vocab_size)
    return tree


class RaggedLfm2:
    """Callable ragged forward bound to a :class:`Lfm2Config`."""

    #: the attention reads pass no scales: int8 pools are refused by the
    #: engine
    supports_quantized_kv = False

    def __init__(self, config: Lfm2Config, block_size: int):
        self.config = config
        self.block_size = block_size
        self.tp = 1

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
        """The per-sequence state the engine's slot pool holds: the tail of
        each convolution layer (flat, ``modules/conv.py``), nothing
        else."""
        cfg = self.config
        return {
            "layers": [i for i in range(cfg.num_hidden_layers)
                       if not cfg.is_attention(i)],
            "leaves": {"conv": (((cfg.conv_L_cache - 1) * cfg.hidden_size,),
                                cfg.dtype)}}

    def __call__(self, params: Dict[str, Any], cache: Dict[str, Any],
                 batch: Dict[str, jax.Array], prefill_tile=None,
                 decode=False):
        """Returns ``(logits [S, vocab], new cache)``.  ``batch`` carries
        ``state_slot`` and ``chunk_start`` beside the usual fields."""
        cfg = self.config
        dt = cfg.dtype
        if not decode and not prefill_tile:
            raise CacheLayoutError(
                "RaggedLfm2 runs decode steps and two-segment (tiled) "
                "batches; a batch packed back to back has no tile a "
                "sequence's convolution tail could be carried along")
        embedding = params["embed_tokens"]["embedding"].astype(dt)
        with jax.named_scope("embed"):
            x = embedding[batch["token_ids"]]
        h, hkv, d = (cfg.num_attention_heads, cfg.num_key_value_heads,
                     cfg.head_dim)
        cos, sin = _rotary(batch["token_pos"], d, cfg.rope_theta)
        new_cache = {}
        for i in range(cfg.num_hidden_layers):
            lp = params[f"layers_{i}"]
            with jax.named_scope(f"layers_{i}"):
                if "conv" in lp:
                    out, new_cache[f"layer_{i}"] = self._conv(
                        lp, x, cache[f"layer_{i}"], batch, prefill_tile)
                else:
                    with jax.named_scope("attn/qkv"):
                        xa = _rms_norm(x, lp["operator_norm"]["scale"],
                                       cfg.norm_eps)
                    out, new_cache[f"layer_{i}"] = ragged_attention_block(
                        lp["self_attn"], xa, cache[f"layer_{i}"], batch,
                        self.block_size, cfg, h, hkv, d, cos, sin,
                        prefill_tile=prefill_tile, decode_mode=decode)
                x = x + out
                mlp = lp["mlp"]
                if "gate" in mlp:           # a router: the routed experts
                    with jax.named_scope("moe/router"):
                        xm = _rms_norm(x, lp["ffn_norm"]["scale"],
                                       cfg.norm_eps)
                    x = x + dropless_moe(
                        xm, mlp, cfg.num_experts_per_tok, dt,
                        renormalize=cfg.norm_topk_prob,
                        routed_scale=cfg.routed_scaling_factor,
                        norm_eps=cfg.router_norm_eps)
                else:
                    with jax.named_scope("mlp"):
                        xm = _rms_norm(x, lp["ffn_norm"]["scale"],
                                       cfg.norm_eps)
                        x = x + qmm(
                            jax.nn.silu(qmm(xm, mlp["gate_proj"]["kernel"],
                                            dt))
                            * qmm(xm, mlp["up_proj"]["kernel"], dt),
                            mlp["down_proj"]["kernel"], dt)
        with jax.named_scope("lm_head"):
            x = _rms_norm(x, params["norm"]["scale"], cfg.norm_eps)
            x = x[batch["logits_idx"]]
            if cfg.tie_embedding:
                logits = x @ embedding.T
            else:
                logits = x @ params["lm_head"]["kernel"].astype(dt)
        return logits, new_cache

    def _conv(self, lp, x, layer_cache, batch, prefill_tile):
        """One gated short convolution over the flat token buffer.  Returns
        ``(out [T, hidden], {"conv": pool})``."""
        cfg, cv, dt = self.config, lp["conv"], self.config.dtype
        with jax.named_scope("conv/in_proj"):
            xn = _rms_norm(x, lp["operator_norm"]["scale"], cfg.norm_eps)
            b, c, u = jnp.split(qmm(xn, cv["in_proj"]["kernel"], dt), 3,
                                axis=-1)
        with jax.named_scope("conv/mix"):
            mixed, pool = _causal_conv(b * u, cv["conv1d"]["kernel"],
                                       layer_cache["conv"], batch,
                                       activation=None,
                                       prefill_tile=prefill_tile)
            mixed = c * mixed
        with jax.named_scope("conv/out_proj"):
            out = qmm(mixed, cv["out_proj"]["kernel"], dt)
        return out, {"conv": pool}
