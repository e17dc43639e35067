"""Ragged LongCat-Flash forward for the FastGen engine (``model_type:
longcat_flash``; LongCat-Flash-Omni's language model is the configuration
served): a published layer is a DOUBLE block with a shortcut-connected
routed branch across it.

One published layer ``l``, on the residual stream ``h``::

    for j in (0, 1):                          # two sub-blocks
        h  = h + MLA_j(RMSNorm(h; input_layernorm[j]))
        m_j = RMSNorm(h; post_attention_layernorm[j])
        if j == 0:  y = MoE(m_0)              # leaves the stream here ...
        h  = h + SwiGLU_j(m_j)                # dense, ffn_hidden_size
    h = h + y                                 # ... and rejoins it here

What is new beside :class:`RaggedDeepseekV3`, the base class whose latent
mixer (``_mla``: the low-rank query, the latent row, the absorbed and
expanded reads and their kernels) this class CALLS on each sub-block, one
mixer in the repository (:class:`LongcatFlashConfig` states what the mixer
reads of a configuration under the mixer's names):

* **Two attention sub-layers a layer, each with its own cache.**  The
  engine builds pools by ``num_layers``, which here is the number of
  attention sub-layers, ``2 x config.num_layers``: sub-block ``j`` of layer
  ``l`` reads and writes cache layer ``2 l + j`` (leaf ``ckv``, 640 lanes at
  the published widths: 2,560 B a token a published layer in bf16).
* **Scaled latents** (``mla_scale_q_lora`` / ``mla_scale_kv_lora``): the
  query times ``sqrt(hidden / q_lora_rank)``, the normalised latent times
  ``sqrt(hidden / kv_lora_rank)`` before it is cached; the shared rotated
  key is not scaled (``q_scale`` / ``kv_scale``, which ``_mla`` applies).
* **A router with zero-compute experts** (``modules/moe.py::
  zero_expert_moe``): ``n_routed_experts + zero_expert_num`` outputs,
  softmax over all of them, the top ``moe_topk`` of ``score + bias``,
  weights the unbiased scores times ``routed_scaling_factor`` with no
  renormalisation; a chosen expert adds ``w E(m)``, a chosen zero output
  adds ``w m`` (``zero_expert_type: identity``; any other is refused by
  name).  The layer holds ``held_experts`` of the experts from
  ``expert_start``, as :class:`RaggedDeepseekV3`; the zero term is the
  token's own chip's and is computed here in full.
* **Counters decided on the device** (``step_counters``): how many routed
  slots chose a zero output or an expert held here is known only where the
  router ran, so the forward returns ``int32[3]`` beside its logits (over
  the REAL rows of the buffer: a pad row writes its cache row to the trash
  block, which is how it is told), and the engine's step programs append it
  to the token vector they already return.

Device scopes under ``layers_<l>``: ``sub_0/attn/...`` and
``sub_1/attn/...`` with the mixer's names beneath (``q_proj``,
``kv_latent``, ``latent_read``, ``expand``, ``prefill_read``, ``out_proj``),
``sub_0/mlp`` and ``sub_1/mlp`` (the post-attention norm and the dense
SwiGLU), and the branch's ``moe/router``, ``moe/dispatch``, ``moe/experts``,
``moe/combine``, ``moe/zero``.  The rotary dims are in the rotate-half
layout (``checkpoint/hf_loader.py`` de-interleaves the published ones).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from deepspeed_tpu.inference.v2.kernels.latent_flash import latent_row_width
from deepspeed_tpu.inference.v2.model_implementations.ragged_deepseek_v3 \
    import RaggedDeepseekV3
from deepspeed_tpu.inference.v2.modules.attention import _rms_norm, _rotary
from deepspeed_tpu.inference.v2.modules.moe import zero_expert_moe
from deepspeed_tpu.ops.quantized_matmul import qmm


@dataclasses.dataclass
class LongcatFlashConfig:
    vocab_size: int = 131072
    hidden_size: int = 6144
    #: the two dense FFNs' width, and an expert's
    ffn_hidden_size: int = 12288
    expert_ffn_hidden_size: int = 2048
    #: PUBLISHED layers: each is two sub-blocks and one routed branch
    num_layers: int = 28
    num_attention_heads: int = 64
    kv_lora_rank: int = 512
    q_lora_rank: int = 1536
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    mla_scale_q_lora: bool = True
    mla_scale_kv_lora: bool = True
    #: the router's experts (every one of the model) and the zero-compute
    #: outputs after them
    n_routed_experts: int = 512
    zero_expert_num: int = 256
    zero_expert_type: str = "identity"
    moe_topk: int = 12
    routed_scaling_factor: float = 6.0
    rope_theta: float = 1e7
    rms_norm_eps: float = 1e-5
    #: the latent norms' eps: the published modelling code's default
    latent_norm_eps: float = 1e-6
    max_position_embeddings: int = 131072
    #: the experts this program holds: ``[expert_start, expert_start +
    #: held_experts)`` of the router's; None = all of them
    held_experts: Optional[int] = None
    expert_start: int = 0
    dtype: Any = jnp.bfloat16

    def __post_init__(self):
        if self.zero_expert_type != "identity":
            raise NotImplementedError(
                f"zero_expert_type={self.zero_expert_type!r}: only the "
                f"identity zero-compute expert (a chosen zero output adds "
                f"its weight times the branch's input) is implemented")

    # -- what the latent mixer reads, under its names ------------------ #
    #: no sparse-attention indexer: the dense latent read
    index_topk = None

    @property
    def q_scale(self) -> float:
        return (self.hidden_size / self.q_lora_rank) ** 0.5 \
            if self.mla_scale_q_lora else 1.0

    @property
    def kv_scale(self) -> float:
        return (self.hidden_size / self.kv_lora_rank) ** 0.5 \
            if self.mla_scale_kv_lora else 1.0

    @property
    def row_width(self) -> int:
        return latent_row_width(self.kv_lora_rank, self.qk_rope_head_dim)


def param_shapes(cfg: LongcatFlashConfig) -> Dict[str, Any]:
    """The parameter tree :class:`RaggedLongcatFlash` reads, as shapes
    (every matrix stored [in, out]; ``kv_b_proj`` columns per head ``k_nope
    | v``).  A layer keeps its two sub-blocks under ``sub_0`` / ``sub_1``
    and the routed branch under ``mlp``: ``gate.wg`` is the router's
    ``classifier`` over experts then zero outputs, its bias beside it."""
    dt, h, hq = cfg.dtype, cfg.hidden_size, cfg.num_attention_heads
    qk = cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
    e = cfg.held_experts or cfg.n_routed_experts
    f, fd = cfg.expert_ffn_hidden_size, cfg.ffn_hidden_size
    width = cfg.n_routed_experts + cfg.zero_expert_num
    sds = lambda *shape: jax.ShapeDtypeStruct(shape, dt)
    kern = lambda i, o: {"kernel": sds(i, o)}

    def sub():
        return {
            "input_layernorm": {"scale": sds(h)},
            "post_attention_layernorm": {"scale": sds(h)},
            "self_attn": {
                "q_a_proj": kern(h, cfg.q_lora_rank),
                "q_a_layernorm": {"scale": sds(cfg.q_lora_rank)},
                "q_b_proj": kern(cfg.q_lora_rank, hq * qk),
                "kv_a_proj_with_mqa": kern(
                    h, cfg.kv_lora_rank + cfg.qk_rope_head_dim),
                "kv_a_layernorm": {"scale": sds(cfg.kv_lora_rank)},
                "kv_b_proj": kern(cfg.kv_lora_rank, hq * (
                    cfg.qk_nope_head_dim + cfg.v_head_dim)),
                "o_proj": kern(hq * cfg.v_head_dim, h)},
            "mlp": {"gate_proj": kern(h, fd), "up_proj": kern(h, fd),
                    "down_proj": kern(fd, h)}}

    layer = lambda: {
        "sub_0": sub(), "sub_1": sub(),
        "mlp": {"gate": {"wg": kern(h, width),
                         "e_score_correction_bias": sds(width)},
                "experts": {"w_gate": sds(e, h, f), "w_up": sds(e, h, f),
                            "w_down": sds(e, f, h)}}}
    return {"embed_tokens": {"embedding": sds(cfg.vocab_size, h)},
            **{f"layers_{i}": layer() for i in range(cfg.num_layers)},
            "norm": {"scale": sds(h)},
            "lm_head": kern(h, cfg.vocab_size)}


class RaggedLongcatFlash(RaggedDeepseekV3):
    """Callable ragged forward bound to a :class:`LongcatFlashConfig`; the
    pool row (``kv_row``), the mixer and its ``interpret`` switch are the
    base class's, which also refuses a mesh in its own words."""

    #: what the forward returns beside its logits, in this order, and the
    #: engine's step programs hand on with the tokens (the module doc)
    step_counters = ("moe_slots", "moe_zero_slots", "moe_held_rows")

    @property
    def num_layers(self):
        """CACHE layers: the attention sub-layers, two a published layer."""
        return 2 * self.config.num_layers

    def __call__(self, params: Dict[str, Any], cache: Dict[str, Any],
                 batch: Dict[str, jax.Array], prefill_tile=None,
                 decode=False):
        """Returns ``(logits [S, vocab], new cache, counters int32[3])``."""
        cfg, dt = self.config, self.config.dtype
        with jax.named_scope("embed"):
            x = params["embed_tokens"]["embedding"].astype(dt)[
                batch["token_ids"]]
        cos, sin = _rotary(batch["token_pos"], cfg.qk_rope_head_dim,
                           cfg.rope_theta)
        # a pad row writes its cache row to the trash block (block 0)
        real = batch["kv_dest"] >= self.block_size
        new_cache, counts = {}, jnp.zeros((3,), jnp.int32)
        for i in range(cfg.num_layers):
            lp = params[f"layers_{i}"]
            with jax.named_scope(f"layers_{i}"):
                for j in (0, 1):
                    sp, name = lp[f"sub_{j}"], f"layer_{2 * i + j}"
                    with jax.named_scope(f"sub_{j}"):
                        out, new_cache[name] = self._mla(
                            sp, x, cache[name], batch, cos, sin,
                            prefill_tile, decode)
                        x = x + out
                        with jax.named_scope("mlp"):
                            m = _rms_norm(
                                x, sp["post_attention_layernorm"]["scale"],
                                cfg.rms_norm_eps)
                    if j == 0:      # the shortcut: kept aside until sub_1
                        y, c = zero_expert_moe(
                            m, lp["mlp"], cfg.moe_topk, dt,
                            cfg.zero_expert_num,
                            expert_start=cfg.expert_start,
                            routed_scale=cfg.routed_scaling_factor,
                            real=real)
                        counts = counts + c
                    with jax.named_scope(f"sub_{j}/mlp"):
                        mlp = sp["mlp"]
                        x = x + qmm(
                            jax.nn.silu(qmm(m, mlp["gate_proj"]["kernel"],
                                            dt))
                            * qmm(m, mlp["up_proj"]["kernel"], dt),
                            mlp["down_proj"]["kernel"], dt)
                with jax.named_scope("moe/combine"):
                    x = x + y
        with jax.named_scope("lm_head"):
            x = _rms_norm(x, params["norm"]["scale"], cfg.rms_norm_eps)
            x = x[batch["logits_idx"]]
            logits = x @ params["lm_head"]["kernel"].astype(dt)
        return logits, new_cache, counts
