"""Ragged AFMoE forward for the FastGen engine (``model_type: afmoe``; Arcee
Trinity is the family served): sliding-window and global attention layers
mixed by ``layer_types``, gated attention under sandwich norms, a sigmoid
router with a selection bias over routed experts beside one shared expert,
leading dense layers.

What is new beside :class:`RaggedLlama` / :class:`RaggedMixtral`:

* **Two kinds of KV layer** (``kv_groups`` below is how the engine learns of
  them).  A ``sliding_attention`` layer lets query ``t`` see key ``j`` iff ``t
  - sliding_window < j <= t`` and rotates q and k (rotate-half,
  ``rope_theta``, no scaling); a ``full_attention`` layer is causal and
  applies NO positional embedding.  The state manager keeps a pool and a
  block table a sequence for each kind (``ragged/ragged_manager.py``): the
  window layers read ``batch["block_tables_win"]`` and write at
  ``batch["kv_dest_win"]``, and their pool holds a sequence's band and no
  more; the global layers keep everything.  The window is a static argument
  of the reads the other models take (``_paged_attention(window=...)``: the
  tiled prefill read, the token-grid kernel, the decode walk), ``None`` in a
  global layer.
* **Gated attention.**  ``g = a W_g`` (its own projection, ``H -> heads x
  head_dim``); the attention output is multiplied by ``sigmoid(g)`` before
  ``o_proj``.  q and k take an RMSNorm per head before the rotation.
* **Sandwich norms.**  ``x' = x + n(Attn(n(x; w_in)); w_post_attn)``; ``x'' =
  x' + n(FFN(n(x'; w_pre_mlp)); w_post_mlp)``: a norm before and after each
  branch.  The embedding is multiplied by ``sqrt(hidden_size)``
  (``mup_enabled``).
* **The router** is the DeepSeek-V3 family's
  (``ops/grouped_gemm.py::sigmoid_bias_topk_routing``): ``s = sigmoid(m
  W_r)`` over every expert, the top-k of ``s + b``, weights ``s`` at the
  chosen experts over their sum + 1e-20 (``route_norm``), times
  ``route_scale``; one shared expert every token takes.  ``n_group`` /
  ``topk_group`` other than 1, ``rope_scaling`` and a ``score_func`` other
  than sigmoid are refused by name.
* **A share of the experts**, as :class:`RaggedDeepseekV3`: the router scores
  all ``num_experts``, the layer holds ``held_experts`` from
  ``expert_start``.

Layout (what ``checkpoint/hf_loader.py`` produces): every matrix [in, out];
``self_attn/gate_proj`` is the attention gate, ``mlp/gate_proj`` the dense
SwiGLU's; experts stacked ``w_gate`` / ``w_up`` ``[E, H, F]``, ``w_down``
``[E, F, H]``; the selection bias ``mlp/gate/e_score_correction_bias``
(published ``expert_bias``); the four norms under their published names.
Device scopes under ``layers_<i>``: ``attn/qkv`` (the input norm, the three
projections, q/k norm), ``attn/rope_insert``, ``attn/swa_read`` or
``attn/full_read`` around the read (chunk part and one-token part alike; the
shared read's own scopes nest inside), ``attn/gate`` (the gate's projection,
its sigmoid and the product), ``attn/out_proj`` (with the post-attention
norm), ``mlp`` on a dense layer, ``moe/router`` (with the pre-MLP norm),
``moe/dispatch``, ``moe/experts``, ``moe/combine``, ``moe/shared``, then
``lm_head``.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Sequence

import jax
import jax.numpy as jnp

from deepspeed_tpu.inference.v2.modules.attention import (
    _paged_attention,
    _rms_norm,
    _rope_insert,
    _rotary,
)
from deepspeed_tpu.inference.v2.modules.moe import dropless_moe
from deepspeed_tpu.ops.quantized_matmul import qmm

F32 = jnp.float32
KINDS = ("sliding_attention", "full_attention")


@dataclasses.dataclass
class AfmoeConfig:
    vocab_size: int = 200192
    hidden_size: int = 3072
    #: the dense SwiGLU of the first ``num_dense_layers`` layers
    intermediate_size: int = 12288
    moe_intermediate_size: int = 3072
    num_hidden_layers: int = 60
    #: "sliding_attention" | "full_attention" a layer; None: the published
    #: pattern, global in every ``global_attn_every_n_layers``-th
    layer_types: Optional[Sequence[str]] = None
    global_attn_every_n_layers: int = 4
    sliding_window: int = 4096
    num_attention_heads: int = 48
    num_key_value_heads: int = 8
    head_dim: int = 128
    num_dense_layers: int = 6
    #: the router's width (every routed expert of the model)
    num_experts: int = 256
    num_experts_per_tok: int = 4
    num_shared_experts: int = 1
    n_group: int = 1
    topk_group: int = 1
    score_func: str = "sigmoid"
    route_norm: bool = True
    route_scale: float = 2.448
    mup_enabled: bool = True
    rope_theta: float = 10000.0
    rope_scaling: Any = None
    rms_norm_eps: float = 1e-5
    max_position_embeddings: int = 262144
    tie_word_embeddings: bool = False
    #: the experts this program holds: ``[expert_start, expert_start +
    #: held_experts)`` of the router's; None = all of them
    held_experts: Optional[int] = None
    expert_start: int = 0
    dtype: Any = jnp.bfloat16

    def __post_init__(self):
        if self.layer_types is None:
            n = self.global_attn_every_n_layers
            self.layer_types = [KINDS[(i + 1) % n == 0]
                                for i in range(self.num_hidden_layers)]
        self.layer_types = list(self.layer_types)
        if len(self.layer_types) != self.num_hidden_layers \
                or set(self.layer_types) - set(KINDS):
            raise ValueError(
                f"layer_types: {self.num_hidden_layers} entries of "
                f"{' | '.join(KINDS)} wanted, got {self.layer_types}")
        if self.n_group != 1 or self.topk_group != 1:
            raise NotImplementedError(
                f"n_group={self.n_group}, topk_group={self.topk_group}: "
                f"group-limited routing (the top experts of the best "
                f"groups only) is not implemented; with one group it is "
                f"the identity, which is what this router computes")
        if self.rope_scaling is not None:
            raise NotImplementedError(
                f"rope_scaling={self.rope_scaling!r}: a scaled rotary "
                f"embedding is not implemented (the published "
                f"configuration has none)")
        if self.score_func != "sigmoid":
            raise NotImplementedError(
                f"score_func={self.score_func!r}: only the sigmoid score "
                f"with a selection bias is implemented")
        if self.tie_word_embeddings:
            raise NotImplementedError(
                "tie_word_embeddings: the head would have to undo the "
                "embedding's sqrt(hidden_size); the published model is "
                "untied")

    def is_window(self, i: int) -> bool:
        return self.layer_types[i] == "sliding_attention"

    def is_moe(self, i: int) -> bool:
        return i >= self.num_dense_layers


def param_shapes(cfg: AfmoeConfig) -> Dict[str, Any]:
    """The parameter tree :class:`RaggedAfmoe` reads, as shapes."""
    dt, h = cfg.dtype, cfg.hidden_size
    hq, hkv, d = cfg.num_attention_heads, cfg.num_key_value_heads, \
        cfg.head_dim
    e = cfg.num_experts
    held = e if cfg.held_experts is None else cfg.held_experts
    f = cfg.moe_intermediate_size
    sds = lambda *shape: jax.ShapeDtypeStruct(shape, dt)
    kern = lambda i, o: {"kernel": sds(i, o)}

    def swiglu(width):
        return {"gate_proj": kern(h, width), "up_proj": kern(h, width),
                "down_proj": kern(width, h)}

    def layer(i):
        mlp = {"gate": {"wg": kern(h, e),
                        "e_score_correction_bias": sds(e)},
               "experts": {"w_gate": sds(held, h, f),
                           "w_up": sds(held, h, f),
                           "w_down": sds(held, f, h)},
               "shared_expert": swiglu(cfg.num_shared_experts * f)} \
            if cfg.is_moe(i) else swiglu(cfg.intermediate_size)
        return {
            "input_layernorm": {"scale": sds(h)},
            "post_attention_layernorm": {"scale": sds(h)},
            "pre_mlp_layernorm": {"scale": sds(h)},
            "post_mlp_layernorm": {"scale": sds(h)},
            "self_attn": {
                "q_proj": kern(h, hq * d), "k_proj": kern(h, hkv * d),
                "v_proj": kern(h, hkv * d), "o_proj": kern(hq * d, h),
                "gate_proj": kern(h, hq * d),
                "q_norm": {"scale": sds(d)}, "k_norm": {"scale": sds(d)}},
            "mlp": mlp}

    return {"embed_tokens": {"embedding": sds(cfg.vocab_size, h)},
            **{f"layers_{i}": layer(i)
               for i in range(cfg.num_hidden_layers)},
            "norm": {"scale": sds(h)},
            "lm_head": kern(h, cfg.vocab_size)}


class RaggedAfmoe:
    """Callable ragged forward bound to an :class:`AfmoeConfig`."""

    #: the reads pass no scales: int8 pools are refused by the engine
    supports_quantized_kv = False

    def __init__(self, config: AfmoeConfig, block_size: int):
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
    def kv_groups(self) -> Dict[str, Dict[str, Any]]:
        """The KV layers the state manager keeps a second pool and block
        table for: the ``window`` group holds the last ``window`` positions
        only; every other layer is global and keeps them all."""
        cfg = self.config
        return {"window": {"layers": [i for i in range(cfg.num_hidden_layers)
                                      if cfg.is_window(i)],
                           "window": int(cfg.sliding_window)}}

    def __call__(self, params: Dict[str, Any], cache: Dict[str, Any],
                 batch: Dict[str, jax.Array], prefill_tile=None,
                 decode=False):
        """Returns ``(logits [S, vocab], new cache)``.  ``batch`` carries
        ``block_tables_win`` and ``kv_dest_win`` beside the usual fields."""
        cfg = self.config
        dt = cfg.dtype
        with jax.named_scope("embed"):
            x = params["embed_tokens"]["embedding"].astype(dt)[
                batch["token_ids"]]
            if cfg.mup_enabled:
                x = x * jnp.asarray(cfg.hidden_size ** 0.5, dt)
        cos, sin = _rotary(batch["token_pos"], cfg.head_dim, cfg.rope_theta)
        # the window layers' view of the batch: their group's table and
        # write targets under the names every read knows
        win = {**batch, "block_tables": batch["block_tables_win"],
               "kv_dest": batch["kv_dest_win"]}
        new_cache = {}
        for i in range(cfg.num_hidden_layers):
            lp = params[f"layers_{i}"]
            window = cfg.is_window(i)
            with jax.named_scope(f"layers_{i}"):
                out, new_cache[f"layer_{i}"] = self._attention(
                    lp, x, cache[f"layer_{i}"], win if window else batch,
                    cos if window else None, sin,
                    cfg.sliding_window if window else None, prefill_tile,
                    decode)
                x = x + out
                x = x + self._ffn(lp, x)
        with jax.named_scope("lm_head"):
            x = _rms_norm(x, params["norm"]["scale"], cfg.rms_norm_eps)
            x = x[batch["logits_idx"]]
            logits = x @ params["lm_head"]["kernel"].astype(dt)
        return logits, new_cache

    def _attention(self, lp, x, layer_cache, batch, cos, sin, window,
                   prefill_tile, decode):
        """One gated attention layer between its two norms; ``cos`` None:
        no positional embedding (a global layer).  Returns ``(branch [T,
        hidden], {"k", "v"})``."""
        cfg, att, dt = self.config, lp["self_attn"], self.config.dtype
        h, hkv, d = (cfg.num_attention_heads, cfg.num_key_value_heads,
                     cfg.head_dim)
        with jax.named_scope("attn/qkv"):
            xa = _rms_norm(x, lp["input_layernorm"]["scale"],
                           cfg.rms_norm_eps)
            q = _rms_norm(qmm(xa, att["q_proj"]["kernel"], dt)
                          .reshape(-1, h, d), att["q_norm"]["scale"],
                          cfg.rms_norm_eps)
            k = _rms_norm(qmm(xa, att["k_proj"]["kernel"], dt)
                          .reshape(-1, hkv, d), att["k_norm"]["scale"],
                          cfg.rms_norm_eps)
            v = qmm(xa, att["v_proj"]["kernel"], dt).reshape(-1, hkv, d)
        with jax.named_scope("attn/rope_insert"):
            q, k_pool, v_pool, _, _, new_cache = _rope_insert(
                q, k, v, cos, sin, layer_cache, batch["kv_dest"])
        with jax.named_scope("attn/swa_read" if window else
                             "attn/full_read"):
            out = _paged_attention(q, k_pool, v_pool, batch,
                                   self.block_size, window=window,
                                   prefill_tile=prefill_tile,
                                   decode_mode=decode)
        with jax.named_scope("attn/gate"):
            gate = qmm(xa, att["gate_proj"]["kernel"], dt)
            out = (out.reshape(-1, h * d).astype(F32)
                   * jax.nn.sigmoid(gate.astype(F32))).astype(dt)
        with jax.named_scope("attn/out_proj"):
            out = _rms_norm(qmm(out, att["o_proj"]["kernel"], dt),
                            lp["post_attention_layernorm"]["scale"],
                            cfg.rms_norm_eps)
        return out, new_cache

    def _ffn(self, lp, x):
        """The FFN branch between its two norms: routed experts plus the
        shared expert where the layer's ``mlp`` holds a router, a dense
        SwiGLU otherwise."""
        cfg, mlp, dt = self.config, lp["mlp"], self.config.dtype
        routed = "gate" in mlp
        with jax.named_scope("moe/router" if routed else "mlp"):
            xm = _rms_norm(x, lp["pre_mlp_layernorm"]["scale"],
                           cfg.rms_norm_eps)
        if routed:
            y = dropless_moe(xm, mlp, cfg.num_experts_per_tok, dt,
                             renormalize=cfg.route_norm,
                             expert_start=cfg.expert_start,
                             routed_scale=cfg.route_scale)
        else:
            with jax.named_scope("mlp"):
                y = qmm(jax.nn.silu(qmm(xm, mlp["gate_proj"]["kernel"], dt))
                        * qmm(xm, mlp["up_proj"]["kernel"], dt),
                        mlp["down_proj"]["kernel"], dt)
        with jax.named_scope("moe/combine" if routed else "mlp"):
            return _rms_norm(y, lp["post_mlp_layernorm"]["scale"],
                             cfg.rms_norm_eps)
