"""Ragged Olmo-Hybrid forward for the FastGen engine (``model_type:
olmo_hybrid``, dense): three Gated DeltaNet (linear-attention) layers to
every softmax-attention layer (``layer_types``), a dense SwiGLU in every
layer, embedding and head untied.

What is new beside :class:`RaggedQwen3Next`, whose call signature, slot
pool and DeltaNet mixer (``modules/gdn.py::gdn_mixer``, the same function)
this model shares:

* **A post-norm block**, the OLMo 2 / OLMo 3 convention: a sub-layer reads
  the RAW residual stream and its OUTPUT is normalised, ``h + norm(f(h))``,
  for the mixer and for the FFN alike (device scope ``post_norm``).  No
  norm sits before either.
* **The DeltaNet shape**: 30 key heads = 30 value heads of 96 keys x 192
  values, neither a multiple of 8 heads nor of 128 lanes; write strengths
  ``beta = 2 sigmoid(b)`` in (0, 2) (``linear_allow_neg_eigval``).  Both go
  through the one ``gdn_step`` / ``gdn_chunk`` of
  ``ops/gated_delta_rule.py``.  Per sequence and layer: a float32 state
  of 30 x 96 x 192 values, 2,211,840 B, stored as 15 head PAIRS ``[15,
  96, 384]`` (``gated_delta_rule.state_leaf_shape``: 192 lanes are one
  and a half of the chip's 128-lane tiles, two heads side by side are
  three whole ones, so ``StateSlotPool.per_sequence_bytes`` is the
  mathematics' count) and a bf16 convolution tail of 3 x 11,520 inputs, flat in one row ``[34560]``
  (``modules/conv.py``).
* **Multi-head attention without positions**: 30 query = 30 KV heads of
  128, RMSNorm over the WHOLE ``q`` and ``k`` projections before the head
  split (``ragged_attention_block``'s rule for a ``q_norm`` as long as the
  projection: OLMoE's), ``cos = sin = None`` (Jamba's and Trinity's global
  layers do the same): ``rope_parameters.rope_theta`` is null in the
  published config, and a non-null one is refused by name.  30 KB a token
  over two attention layers of eight: past ~450 tokens a sequence's keys
  outweigh its 13.7 MB of state, so BOTH pools are large.
* ``tp = 1``: 30 heads split over neither 4 nor 8 chips; a mesh with a
  ``model`` axis is refused by name.

Static branches are on a layer's own parameters: one with ``linear_attn``
is a DeltaNet layer.  Decode steps and two-segment (tiled) batches only, as
every model with state slots.

Layout: every matrix [in, out]; the mixer's as ``modules/gdn.py`` says;
``q_norm`` / ``k_norm`` ``[heads x head_dim]``.  The published tensor
names of ``olmo_hybrid`` are in no file of this repository, so
``checkpoint/hf_loader.py`` has no rules for it: weights are the caller's.
Device scopes under ``layers_<i>``: ``gdn/in_proj``, ``gdn/conv``,
``gdn/rule``, ``gdn/out``; ``attn/*`` as RaggedLlama; ``mlp``;
``post_norm`` (both output norms and their residual adds).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from deepspeed_tpu.inference.v2.modules.attention import (
    _rms_norm,
    ragged_attention_block,
)
from deepspeed_tpu.inference.v2.modules.gdn import (
    gdn_mixer,
    gdn_param_shapes,
    gdn_state_leaves,
)
from deepspeed_tpu.inference.v2.ragged.kv_cache import CacheLayoutError
from deepspeed_tpu.ops.quantized_matmul import qmm

LAYER_KINDS = ("linear_attention", "full_attention")


@dataclasses.dataclass
class OlmoHybridConfig:
    vocab_size: int = 100352
    hidden_size: int = 3840
    intermediate_size: int = 11008
    num_hidden_layers: int = 32
    num_attention_heads: int = 30
    num_key_value_heads: int = 30
    #: one kind a layer; None: ``full_attention`` where ``l % 4 == 3``
    layer_types: Optional[Tuple[str, ...]] = None
    linear_num_key_heads: int = 30
    linear_num_value_heads: int = 30
    linear_key_head_dim: int = 96
    linear_value_head_dim: int = 192
    linear_conv_kernel_dim: int = 4
    #: write strengths ``2 sigmoid(b)``
    linear_allow_neg_eigval: bool = True
    rms_norm_eps: float = 1e-6
    #: ``rope_parameters.rope_theta``: null as published, no positions
    rope_theta: Optional[float] = None
    max_position_embeddings: int = 65536
    tie_word_embeddings: bool = False
    dtype: Any = jnp.bfloat16
    # read by the shared attention block
    sliding_window: Optional[int] = None

    def __post_init__(self):
        if self.layer_types is None:
            self.layer_types = tuple(
                LAYER_KINDS[l % 4 == 3]
                for l in range(self.num_hidden_layers))
        self.layer_types = tuple(self.layer_types)
        for l, kind in enumerate(self.layer_types):
            if kind not in LAYER_KINDS:
                raise NotImplementedError(
                    f"layer_types[{l}] = {kind!r}: an olmo_hybrid layer is "
                    f"one of {LAYER_KINDS}")
        if len(self.layer_types) != self.num_hidden_layers:
            raise ValueError(
                f"layer_types names {len(self.layer_types)} layers, "
                f"num_hidden_layers is {self.num_hidden_layers}")
        if self.rope_theta is not None:
            raise NotImplementedError(
                f"rope_parameters.rope_theta = {self.rope_theta}: the "
                f"published olmo_hybrid attention has no positional "
                f"embedding (rope_theta null); a rotary variant is not "
                f"implemented")
        if self.linear_num_value_heads % self.linear_num_key_heads:
            raise ValueError(
                f"linear_num_value_heads={self.linear_num_value_heads} is "
                f"no multiple of linear_num_key_heads="
                f"{self.linear_num_key_heads}")

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    def is_attention(self, i: int) -> bool:
        return self.layer_types[i] == "full_attention"


def param_shapes(cfg: OlmoHybridConfig) -> Dict[str, Any]:
    """The parameter tree :class:`RaggedOlmoHybrid` reads, as shapes."""
    dt, h, f = cfg.dtype, cfg.hidden_size, cfg.intermediate_size
    hq, hkv, d = cfg.num_attention_heads, cfg.num_key_value_heads, \
        cfg.head_dim
    sds = lambda *shape: jax.ShapeDtypeStruct(shape, dt)
    kern = lambda i, o: {"kernel": sds(i, o)}

    def layer(i):
        mixer = {"self_attn": {
            "q_proj": kern(h, hq * d), "k_proj": kern(h, hkv * d),
            "v_proj": kern(h, hkv * d), "o_proj": kern(hq * d, h),
            "q_norm": {"scale": sds(hq * d)},
            "k_norm": {"scale": sds(hkv * d)}}} \
            if cfg.is_attention(i) else {
                "linear_attn": gdn_param_shapes(cfg, sds)}
        return {**mixer,
                "post_attention_layernorm": {"scale": sds(h)},
                "post_feedforward_layernorm": {"scale": sds(h)},
                "mlp": {"gate_proj": kern(h, f), "up_proj": kern(h, f),
                        "down_proj": kern(f, h)}}

    tree = {"embed_tokens": {"embedding": sds(cfg.vocab_size, h)},
            **{f"layers_{i}": layer(i)
               for i in range(cfg.num_hidden_layers)},
            "norm": {"scale": sds(h)}}
    if not cfg.tie_word_embeddings:
        tree["lm_head"] = kern(h, cfg.vocab_size)
    return tree


class RaggedOlmoHybrid:
    """Callable ragged forward bound to an :class:`OlmoHybridConfig`."""

    #: the cache tree is not one shape a layer; int8 KV is not wired
    supports_quantized_kv = False

    def __init__(self, config: OlmoHybridConfig, block_size: int, mesh=None):
        if mesh is not None and dict(mesh.shape).get("model", 1) > 1:
            raise ValueError(
                f"RaggedOlmoHybrid serves tp = 1: "
                f"{config.num_attention_heads} heads split over neither 4 "
                f"nor 8 chips (mesh 'model' axis = "
                f"{dict(mesh.shape)['model']})")
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
        return {"layers": [i for i in range(cfg.num_hidden_layers)
                           if not cfg.is_attention(i)],
                "leaves": gdn_state_leaves(cfg)}

    def __call__(self, params: Dict[str, Any], cache: Dict[str, Any],
                 batch: Dict[str, jax.Array], prefill_tile=None,
                 decode=False):
        """Returns ``(logits [S, vocab], new cache)``.  ``batch`` carries
        ``state_slot`` and ``chunk_start`` beside the usual fields."""
        cfg = self.config
        dt, eps = cfg.dtype, cfg.rms_norm_eps
        if not decode and not prefill_tile:
            raise CacheLayoutError(
                "RaggedOlmoHybrid runs decode steps and two-segment (tiled) "
                "batches; a batch packed back to back has no tile a "
                "sequence's state could be carried along")
        embedding = params["embed_tokens"]["embedding"].astype(dt)
        with jax.named_scope("embed"):
            x = embedding[batch["token_ids"]]
        h, hkv, d = (cfg.num_attention_heads, cfg.num_key_value_heads,
                     cfg.head_dim)
        new_cache = {}
        for i in range(cfg.num_hidden_layers):
            lp = params[f"layers_{i}"]
            with jax.named_scope(f"layers_{i}"):
                # both mixers read the raw stream: no norm before them
                if "linear_attn" in lp:
                    out, new_cache[f"layer_{i}"] = gdn_mixer(
                        lp["linear_attn"], x, cache[f"layer_{i}"], batch,
                        prefill_tile, cfg, interpret=self.interpret)
                else:
                    # no positional embedding: cos = sin = None
                    out, new_cache[f"layer_{i}"] = ragged_attention_block(
                        lp["self_attn"], x, cache[f"layer_{i}"], batch,
                        self.block_size, cfg, h, hkv, d, None, None,
                        prefill_tile=prefill_tile, decode_mode=decode)
                with jax.named_scope("post_norm"):
                    x = x + _rms_norm(
                        out, lp["post_attention_layernorm"]["scale"], eps)
                with jax.named_scope("mlp"):
                    mlp = lp["mlp"]
                    f = qmm(
                        jax.nn.silu(qmm(x, mlp["gate_proj"]["kernel"], dt))
                        * qmm(x, mlp["up_proj"]["kernel"], dt),
                        mlp["down_proj"]["kernel"], dt)
                with jax.named_scope("post_norm"):
                    x = x + _rms_norm(
                        f, lp["post_feedforward_layernorm"]["scale"], eps)
        with jax.named_scope("lm_head"):
            x = _rms_norm(x, params["norm"]["scale"], eps)
            x = x[batch["logits_idx"]]
            if cfg.tie_word_embeddings:
                logits = x @ embedding.T
            else:
                logits = x @ params["lm_head"]["kernel"].astype(dt)
        return logits, new_cache
