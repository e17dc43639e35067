"""Ragged DeepSeek-V3-family forward for the FastGen engine (``model_type:
deepseek_v3``, Moonlight-16B-A3B, and ``glm_moe_dsa``, GLM-5, are the
configurations served): multi-head latent attention (MLA), dense or behind a
learned sparse-attention indexer, a sigmoid router with a learned selection
bias over routed experts beside ungated shared experts, and leading dense
layers.

What is new beside :class:`RaggedLlama` / :class:`RaggedMixtral`:

* **A latent row in the paged pool.**  Per token and layer the cache keeps
  ``[c | k_pe | 0]``: the RMS-normalised latent (``kv_lora_rank`` values)
  and ONE rotated key of ``qk_rope_head_dim`` values shared by every head,
  padded to whole 128-lane tiles (``kv_row`` below is how the engine learns
  of it): 640 bf16 values = 1,280 B at the published widths, of which 1,152
  are content, against 10,240 B for the same 16 heads kept as keys and
  values.  One leaf, so that a read moves the row once and a block
  operation moves one array; padded, because the device lays a 576-lane
  minor dimension out in five tiles whether or not the sixty-four spare
  lanes are named.
* **Two arithmetic paths over that row** (``kernels/latent_flash.py``).  A
  (query, key) pair costs ``H x (row + rank) x 2`` FLOP absorbed (34.8 k at
  the published widths) and ``H x (qk + v) x 2`` expanded (10.2 k) plus
  ``rank x H x (nope + v) x 2`` per context row to expand it once per chunk
  (4.1 k a pair at a 1,024-token chunk).  A one-token row has nothing to
  amortise the expansion over and is bound by the bytes it reads (30 FLOP/B
  absorbed, under the chip's 240), so: **one-token rows** (a decode step;
  the first ``S`` rows of a two-segment batch) take the **absorbed** path,
  the **tile segment** the **expanded** one.  Off the TPU, and at widths the
  kernels cannot tile, both are XLA compositions of the same mathematics
  (also the kernels' parity oracles); a batch packed back to back (an
  engine whose budget is no whole number of tiles) takes the expanded
  composition for every row.
* **The router** (``ops/grouped_gemm.py::sigmoid_bias_topk_routing``):
  ``s = sigmoid(x W_g)`` over every expert, the top-k of ``s + b``, weights
  ``s`` at the chosen experts, renormalised and scaled.  Group-limited
  selection (``n_group`` > 1) is refused by name.
* **An FFN that differs by layer**, read from the layer's own parameters: a
  layer whose ``mlp`` holds a router is routed experts plus the shared
  experts (one ungated SwiGLU of width ``n_shared_experts x
  moe_intermediate_size``), any other a dense SwiGLU.
* **A share of the experts**, as :class:`RaggedQwen3Next`: the router scores
  all ``n_routed_experts``, the layer holds ``held_experts`` from
  ``expert_start``.

* **A low-rank query** (``q_lora_rank``): ``q = RMSNorm(x W_qa) W_qb``;
  None: ``q_proj`` is one matrix.
* **Scaled latents** (``q_scale``, ``kv_scale``; LongCat-Flash, whose
  double block ``ragged_longcat_flash.py`` builds around ``_mla``): the
  query times one constant, the normalised latent times another BEFORE it
  is cached, so the row, ``W_kvb`` and the kernels are used as they are.
* **A learned sparse-attention indexer** (``index_topk``; DeepSeek Sparse
  Attention, ``kernels/sparse_latent.py``): a token keeps a SECOND pool row,
  its indexer key (``kv_row`` then states two leaves, ``ckv`` and
  ``idx_k``), every query row scores all of its sequence's cached
  positions against that leaf, ``sum_j w_j relu(qI_j . kI_s)``, and reads
  the exact top ``index_topk`` latent rows alone: the tile segment through
  a mask over blocks its rows share, one-token rows token by token through
  the table; both absorbed.  The indexer's queries come from the query
  latent, so it needs ``q_lora_rank``.  ``index_topk`` None: the dense
  read, every program as it was.

The rotary dims are in the rotate-half layout (the published checkpoint
stores them interleaved; ``checkpoint/hf_loader.py`` de-interleaves).
Device scopes under ``layers_<i>``: ``attn/q_proj`` (norm and ``W_q``, or
``W_qa``, its norm and ``W_qb``),
``attn/kv_latent`` (``W_kva``, the latent norm, rotary, the insert),
``attn/latent_read`` (one-token rows: absorb into q, the walk, ``W_uv``),
``attn/expand`` and ``attn/prefill_read`` (the tile segment),
``attn/gate`` (a layer whose ``self_attn`` holds ``gate_proj``: one sigmoid
scalar a head on the read's output), ``attn/out_proj``; with an indexer ``attn/index_k`` (``W_Ik``, LayerNorm,
rotary, the insert into ``idx_k``), ``attn/index_score`` (``W_Iq``, ``W_Iw``,
the scores), ``attn/index_topk`` and ``attn/sparse_read`` (both segments) in
place of the three reads; ``mlp`` on a dense layer; ``moe/router``,
``moe/dispatch``, ``moe/experts``, ``moe/combine``, ``moe/shared``.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from deepspeed_tpu.inference.v2.kernels.latent_flash import (
    latent_decode_attention, latent_expand, latent_kernels_usable,
    latent_prefill_attention, latent_row_width)
from deepspeed_tpu.inference.v2.kernels.sparse_latent import (
    gathered_latent_read, index_scores, masked_latent_read, select_threshold,
    select_topk, sort_key, sparse_tile_read, sparse_tile_read_usable)
from deepspeed_tpu.inference.v2.modules.attention import (_layer_norm,
                                                          _rms_norm, _rotary)
from deepspeed_tpu.inference.v2.modules.moe import dropless_moe
from deepspeed_tpu.models.llama import apply_rotary
from deepspeed_tpu.ops.quantized_matmul import qmm
from deepspeed_tpu.utils.platform import on_tpu

F32 = jnp.float32


@dataclasses.dataclass
class DeepseekV3Config:
    vocab_size: int = 163840
    hidden_size: int = 2048
    intermediate_size: int = 11264
    moe_intermediate_size: int = 1408
    num_hidden_layers: int = 27
    num_attention_heads: int = 16
    kv_lora_rank: int = 512
    q_lora_rank: Optional[int] = None
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    #: the router's width (every routed expert of the model)
    n_routed_experts: int = 64
    n_shared_experts: int = 2
    num_experts_per_tok: int = 6
    first_k_dense_replace: int = 1
    moe_layer_freq: int = 1
    n_group: int = 1
    topk_group: int = 1
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 2.446
    scoring_func: str = "sigmoid"
    topk_method: str = "noaux_tc"
    rope_theta: float = 50000.0
    rms_norm_eps: float = 1e-5
    #: the latent norms' eps (``kv_a_layernorm``, ``q_a_layernorm``): the
    #: published modelling code builds them with its default, which
    #: ``config.json`` does not carry
    latent_norm_eps: float = 1e-6
    #: what the query (after ``q_b_proj``) and the normalised latent are
    #: multiplied by (LongCat-Flash's ``mla_scale_q_lora`` /
    #: ``mla_scale_kv_lora``); 1: nothing is
    q_scale: float = 1.0
    kv_scale: float = 1.0
    #: the sparse-attention indexer (``glm_moe_dsa``): its heads, their
    #: width, and how many cached positions a query row reads; ``index_topk``
    #: None = no indexer, the dense latent read
    index_n_heads: int = 32
    index_head_dim: int = 128
    index_topk: Optional[int] = None
    #: eps of the indexer key's LayerNorm (``k_norm``)
    index_norm_eps: float = 1e-6
    max_position_embeddings: int = 8192
    #: the experts this program holds: ``[expert_start, expert_start +
    #: held_experts)`` of the router's; None = all of them
    held_experts: Optional[int] = None
    expert_start: int = 0
    dtype: Any = jnp.bfloat16

    def __post_init__(self):
        if self.n_group != 1 or self.topk_group != 1:
            raise NotImplementedError(
                f"n_group={self.n_group}, topk_group={self.topk_group}: "
                f"group-limited routing (the top experts of the best "
                f"groups only) is not implemented; with one group it is "
                f"the identity, which is what this router computes")
        if self.scoring_func != "sigmoid" or self.topk_method != "noaux_tc":
            raise NotImplementedError(
                f"scoring_func={self.scoring_func!r}, topk_method="
                f"{self.topk_method!r}: only the sigmoid score with a "
                f"selection bias (noaux_tc) is implemented")
        if self.index_topk is not None and self.q_lora_rank is None:
            raise NotImplementedError(
                f"index_topk={self.index_topk} without q_lora_rank: the "
                f"indexer's queries are made from the query latent")
        if self.index_topk is not None and (
                self.index_head_dim % 128
                or self.qk_rope_head_dim > self.index_head_dim):
            raise NotImplementedError(
                f"index_head_dim={self.index_head_dim}: the indexer key is "
                f"a pool row of whole 128-lane tiles whose first "
                f"qk_rope_head_dim ({self.qk_rope_head_dim}) values rotate")

    def is_moe(self, i: int) -> bool:
        return i >= self.first_k_dense_replace \
            and i % self.moe_layer_freq == 0

    @property
    def row_width(self) -> int:
        return latent_row_width(self.kv_lora_rank, self.qk_rope_head_dim)


def param_shapes(cfg: DeepseekV3Config) -> Dict[str, Any]:
    """The parameter tree :class:`RaggedDeepseekV3` reads, as shapes (every
    matrix stored [in, out]; ``kv_b_proj`` columns per head ``k_nope | v``;
    an indexer head's rotated dims come first)."""
    dt, h, hq = cfg.dtype, cfg.hidden_size, cfg.num_attention_heads
    qk = cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
    e = cfg.held_experts or cfg.n_routed_experts
    f = cfg.moe_intermediate_size
    sds = lambda *shape: jax.ShapeDtypeStruct(shape, dt)
    kern = lambda i, o: {"kernel": sds(i, o)}
    qr = cfg.q_lora_rank
    q_path = {"q_proj": kern(h, hq * qk)} if qr is None else {
        "q_a_proj": kern(h, qr), "q_a_layernorm": {"scale": sds(qr)},
        "q_b_proj": kern(qr, hq * qk)}
    if cfg.index_topk is not None:
        hi, di = cfg.index_n_heads, cfg.index_head_dim
        q_path["indexer"] = {
            "wq_b": kern(qr, hi * di), "wk": kern(h, di),
            "k_norm": {"scale": sds(di), "bias": sds(di)},
            "weights_proj": kern(h, hi)}
    swiglu = lambda width: {"gate_proj": kern(h, width),
                            "up_proj": kern(h, width),
                            "down_proj": kern(width, h)}

    def layer(i):
        mlp = swiglu(cfg.intermediate_size) if not cfg.is_moe(i) else {
            "gate": {"wg": kern(h, cfg.n_routed_experts),
                     "e_score_correction_bias": sds(cfg.n_routed_experts)},
            "experts": {"w_gate": sds(e, h, f), "w_up": sds(e, h, f),
                        "w_down": sds(e, f, h)},
            "shared_expert": swiglu(cfg.n_shared_experts * f)}
        return {
            "input_layernorm": {"scale": sds(h)},
            "post_attention_layernorm": {"scale": sds(h)},
            "self_attn": {
                **q_path,
                "kv_a_proj_with_mqa": kern(
                    h, cfg.kv_lora_rank + cfg.qk_rope_head_dim),
                "kv_a_layernorm": {"scale": sds(cfg.kv_lora_rank)},
                "kv_b_proj": kern(cfg.kv_lora_rank, hq * (
                    cfg.qk_nope_head_dim + cfg.v_head_dim)),
                "o_proj": kern(hq * cfg.v_head_dim, h)},
            "mlp": mlp}

    return {"embed_tokens": {"embedding": sds(cfg.vocab_size, h)},
            **{f"layers_{i}": layer(i)
               for i in range(cfg.num_hidden_layers)},
            "norm": {"scale": sds(h)},
            "lm_head": kern(h, cfg.vocab_size)}


def _context(pool, tables, slot, block_size):
    """Each row's context through its sequence's block table: [R, C, W]
    (context index == absolute position)."""
    s_count, b = tables.shape
    flat = (tables[:, :, None] * block_size
            + jnp.arange(block_size, dtype=jnp.int32)[None, None, :]
            ).reshape(s_count, b * block_size)
    return pool[flat[slot]]


def _softmax_rows(scores, pos):
    """Causal mask by position and a float32 softmax; a pad row (position
    -1) sees nothing and comes out finite."""
    keep = jnp.arange(scores.shape[-1], dtype=jnp.int32)[None, :] \
        <= pos[:, None]
    return jax.nn.softmax(jnp.where(keep[:, None, :], scores, -1e30), -1)


def absorbed_read_xla(q_cat, pool, tables, slot, pos, block_size, rank,
                      scale):
    """The absorbed form as an XLA composition: q_cat [R, H, W] against each
    row's gathered context; returns ``sum p c`` [R, H, rank]."""
    ctx = _context(pool, tables, slot, block_size)
    scores = jnp.einsum("rhw,rcw->rhc", q_cat, ctx,
                        preferred_element_type=F32) * scale
    probs = _softmax_rows(scores, pos)
    return jnp.einsum("rhc,rcv->rhv", probs.astype(ctx.dtype),
                      ctx[..., :rank],
                      preferred_element_type=F32).astype(q_cat.dtype)


def expanded_read_xla(q_nope, q_pe, pool, w_kvb, tables, slot, pos,
                      block_size, rank, scale):
    """The expanded form as an XLA composition: each row's context
    expanded by ``W_kvb`` to per-head keys and values; [R, H, v_head_dim]."""
    r, h, nope = q_nope.shape
    rope = q_pe.shape[-1]
    ctx = _context(pool, tables, slot, block_size)
    kv = jnp.einsum("rcl,ln->rcn", ctx[..., :rank], w_kvb,
                    preferred_element_type=F32).astype(pool.dtype)
    kv = kv.reshape(r, ctx.shape[1], h, -1)
    scores = (jnp.einsum("rhd,rchd->rhc", q_nope, kv[..., :nope],
                         preferred_element_type=F32)
              + jnp.einsum("rhd,rcd->rhc", q_pe, ctx[..., rank:rank + rope],
                           preferred_element_type=F32)) * scale
    probs = _softmax_rows(scores, pos)
    return jnp.einsum("rhc,rchd->rhd", probs.astype(kv.dtype),
                      kv[..., nope:],
                      preferred_element_type=F32).astype(q_nope.dtype)


class RaggedDeepseekV3:
    """Callable ragged forward bound to a :class:`DeepseekV3Config`."""

    #: ``quantize_kv`` is per KV head; a latent row has none
    supports_quantized_kv = False

    def __init__(self, config: DeepseekV3Config, block_size: int,
                 mesh=None):
        if mesh is not None:
            raise NotImplementedError(
                "RaggedDeepseekV3 serves one chip (tp = 1): the latent row "
                "has one head, so a head split would copy the cache to "
                "every chip; data-parallel attention over replicas is how "
                "this family is sharded")
        self.config = config
        self.block_size = block_size
        self.tp = 1
        #: None: the Mosaic kernels on a TPU (at widths they can tile), the
        #: XLA compositions elsewhere; tests pass True (interpret mode)
        self.interpret: Optional[bool] = None

    @property
    def num_layers(self):
        return self.config.num_hidden_layers

    @property
    def num_kv_heads(self):
        return 1

    @property
    def head_dim(self):
        return self.config.row_width

    @property
    def kv_row(self) -> Dict[str, int]:
        """What the paged pool keeps per token and layer for this model,
        instead of per-head keys and values: leaf name -> lanes.  With an
        indexer a second, narrower leaf: its key."""
        row = {"ckv": self.config.row_width}
        if self.index_topk is not None:
            row["idx_k"] = self.config.index_head_dim
        return row

    @property
    def index_topk(self) -> Optional[int]:
        """Cached positions a query row reads (None: all of them); what the
        engine counts ``idx_*`` / ``sel_*`` by."""
        return self.config.index_topk

    # ------------------------------------------------------------------ #
    def __call__(self, params: Dict[str, Any], cache: Dict[str, Any],
                 batch: Dict[str, jax.Array], prefill_tile=None,
                 decode=False):
        """Returns ``(logits [S, vocab], new cache)``."""
        cfg = self.config
        dt = cfg.dtype
        with jax.named_scope("embed"):
            x = params["embed_tokens"]["embedding"].astype(dt)[
                batch["token_ids"]]
        cos, sin = _rotary(batch["token_pos"], cfg.qk_rope_head_dim,
                           cfg.rope_theta)
        new_cache = {}
        for i in range(cfg.num_hidden_layers):
            lp = params[f"layers_{i}"]
            with jax.named_scope(f"layers_{i}"):
                out, new_cache[f"layer_{i}"] = self._mla(
                    lp, x, cache[f"layer_{i}"], batch, cos, sin,
                    prefill_tile, decode)
                x = self._ffn(lp, x + out)
        return self._head(params, x, batch), new_cache

    def _ffn(self, lp, x):
        """``x`` plus the layer's FFN branch: routed experts beside the
        shared ones where its ``mlp`` holds a router, else a dense
        SwiGLU."""
        cfg, dt, mlp = self.config, self.config.dtype, lp["mlp"]
        if "gate" in mlp:       # a router: routed + shared experts
            with jax.named_scope("moe/router"):
                xm = _rms_norm(x, lp["post_attention_layernorm"]["scale"],
                               cfg.rms_norm_eps)
            return x + dropless_moe(
                xm, mlp, cfg.num_experts_per_tok, dt,
                renormalize=cfg.norm_topk_prob,
                expert_start=cfg.expert_start,
                routed_scale=cfg.routed_scaling_factor)
        with jax.named_scope("mlp"):
            xm = _rms_norm(x, lp["post_attention_layernorm"]["scale"],
                           cfg.rms_norm_eps)
            return x + qmm(
                jax.nn.silu(qmm(xm, mlp["gate_proj"]["kernel"], dt))
                * qmm(xm, mlp["up_proj"]["kernel"], dt),
                mlp["down_proj"]["kernel"], dt)

    def _head(self, params, x, batch):
        """The final norm and the head over the rows ``logits_idx`` names."""
        cfg = self.config
        with jax.named_scope("lm_head"):
            x = _rms_norm(x, params["norm"]["scale"], cfg.rms_norm_eps)
            x = x[batch["logits_idx"]]
            return x @ params["lm_head"]["kernel"].astype(cfg.dtype)

    def _mla(self, lp, x, layer_cache, batch, cos, sin, prefill_tile,
             decode):
        """One latent-attention mixer over the flat token buffer.  Returns
        ``(out [T, hidden], the layer's pool leaves)``."""
        cfg, att, dt = self.config, lp["self_attn"], self.config.dtype
        h, rank = cfg.num_attention_heads, cfg.kv_lora_rank
        nope, rope, vd = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, \
            cfg.v_head_dim
        width = cfg.row_width
        t_rows = x.shape[0]
        with jax.named_scope("attn/q_proj"):
            xa = _rms_norm(x, lp["input_layernorm"]["scale"],
                           cfg.rms_norm_eps)
            if cfg.q_lora_rank is None:
                cq = None
                q = qmm(xa, att["q_proj"]["kernel"], dt)
            else:
                cq = _rms_norm(qmm(xa, att["q_a_proj"]["kernel"], dt),
                               att["q_a_layernorm"]["scale"],
                               cfg.latent_norm_eps)
                q = qmm(cq, att["q_b_proj"]["kernel"], dt)
            if cfg.q_scale != 1.0:
                q = (q.astype(F32) * cfg.q_scale).astype(dt)
            q = q.reshape(t_rows, h, nope + rope)
            q_nope = q[..., :nope]
        with jax.named_scope("attn/kv_latent"):
            kva = qmm(xa, att["kv_a_proj_with_mqa"]["kernel"], dt)
            c = _rms_norm(kva[:, :rank], att["kv_a_layernorm"]["scale"],
                          cfg.latent_norm_eps)
            if cfg.kv_scale != 1.0:     # the row holds the SCALED latent
                c = (c.astype(F32) * cfg.kv_scale).astype(dt)
            # ONE rotated key a token, shared by every head
            k_pe = apply_rotary(kva[:, None, rank:], cos, sin)[:, 0]
            q_pe = apply_rotary(q[..., nope:], cos, sin)
            row = jnp.concatenate(
                [c, k_pe, jnp.zeros((t_rows, width - rank - rope), dt)], -1)
            pool = layer_cache["ckv"].at[batch["kv_dest"]].set(
                row.astype(layer_cache["ckv"].dtype))
        new_cache = {"ckv": pool}
        if cfg.index_topk is None:
            out = self._latent_read(att, q_nope, q_pe, pool, batch,
                                    prefill_tile, decode)
        else:
            out, new_cache["idx_k"] = self._sparse_read(
                att, xa, cq, q_nope, q_pe, pool, layer_cache["idx_k"], batch,
                cos, sin, prefill_tile, decode)
        if "gate_proj" in att:      # a gate a head (dots3_note)
            out = self._head_gate(att, xa, out)
        with jax.named_scope("attn/out_proj"):
            out = qmm(out.reshape(t_rows, h * vd), att["o_proj"]["kernel"],
                      dt)
        return out, new_cache

    def _head_gate(self, att, xa, out):
        """``out [T, H, v_head_dim]`` times one sigmoid scalar a head, ``g =
        sigmoid(x_n W_g)`` from the layer's normed input."""
        dt = self.config.dtype
        with jax.named_scope("attn/gate"):
            g = jax.nn.sigmoid(
                qmm(xa, att["gate_proj"]["kernel"], dt).astype(F32))
            return (out.astype(F32) * g[:, :, None]).astype(dt)

    def _latent_read(self, att, q_nope, q_pe, pool, batch, prefill_tile,
                     decode):
        """The read of EVERY cached row up to a row's position (no indexer):
        ``out [T, H, v_head_dim]``.  One-token rows absorbed, the tile
        segment expanded."""
        cfg, dt, bs = self.config, self.config.dtype, self.block_size
        h, rank = cfg.num_attention_heads, cfg.kv_lora_rank
        nope, rope, vd = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, \
            cfg.v_head_dim
        width = cfg.row_width
        scale = float((nope + rope) ** -0.5)
        tables, slot, pos = batch["block_tables"], batch["token_slot"], \
            batch["token_pos"]
        t_rows, s_rows = q_nope.shape[0], tables.shape[0]
        w_kvb = att["kv_b_proj"]["kernel"].astype(dt)
        kernels = self.interpret
        if kernels is None:
            kernels = on_tpu() and latent_kernels_usable(rank, nope, vd, bs)
        interpret = bool(self.interpret)

        def absorbed(rows):
            """One-token rows: absorb W_uk into q, read, apply W_uv."""
            w3 = w_kvb.reshape(rank, h, nope + vd)
            q_lat = jnp.einsum("thd,chd->thc", q_nope[rows],
                               w3[..., :nope],
                               preferred_element_type=F32).astype(dt)
            n = q_lat.shape[0]
            q_cat = jnp.concatenate(
                [q_lat, q_pe[rows],
                 jnp.zeros((n, h, width - rank - rope), dt)], -1)
            if kernels:
                o_lat = latent_decode_attention(
                    q_cat, pool, tables, slot[rows], pos[rows],
                    block_size=bs, value_dim=rank, scale=scale,
                    interpret=interpret)
            else:
                o_lat = absorbed_read_xla(q_cat, pool, tables, slot[rows],
                                          pos[rows], bs, rank, scale)
            return jnp.einsum("thc,chd->thd", o_lat, w3[..., nope:],
                              preferred_element_type=F32).astype(dt)

        def expanded(rows):
            """Prompt chunks: expand the context, attend per head."""
            if not (kernels and prefill_tile):
                with jax.named_scope("attn/prefill_read"):
                    return expanded_read_xla(
                        q_nope[rows], q_pe[rows], pool, w_kvb, tables,
                        slot[rows], pos[rows], bs, rank, scale)
            with jax.named_scope("attn/expand"):
                kv, plan = latent_expand(
                    pool, w_kvb, tables, slot[rows], pos[rows],
                    block_size=bs, tile_q=int(prefill_tile), rank=rank,
                    interpret=interpret)
            with jax.named_scope("attn/prefill_read"):
                n = t_rows - s_rows
                q_cat = jnp.concatenate(
                    [q_nope[rows], q_pe[rows],
                     jnp.zeros((n, h, 128 - rope), dt)], -1)
                return latent_prefill_attention(
                    q_cat, kv, plan, pos[rows], block_size=bs,
                    tile_q=int(prefill_tile), nope=nope, v_dim=vd,
                    scale=scale, interpret=interpret)

        if decode:
            with jax.named_scope("attn/latent_read"):
                out = absorbed(slice(0, t_rows))
        elif prefill_tile:
            with jax.named_scope("attn/latent_read"):
                out = absorbed(slice(0, s_rows))
            if t_rows > s_rows:             # the tile segment
                out = jnp.concatenate(
                    [out, expanded(slice(s_rows, t_rows))])
        else:
            out = expanded(slice(0, t_rows))
        return out

    def _sparse_read(self, att, xa, cq, q_nope, q_pe, pool, idx_pool, batch,
                     cos, sin, prefill_tile, decode):
        """The indexer and the read of what it selects, for every row of
        the buffer: ``(out [T, H, v_head_dim], the idx_k pool)``.  Rows are
        taken in groups that share a block table: the one-token rows one
        each (a gather of the selected rows), the tile segment a tile each
        (a mask over the blocks its rows share); a batch packed back to
        back (no tiles) is one-token groups throughout."""
        cfg, dt, bs = self.config, self.config.dtype, self.block_size
        ix = att["indexer"]
        h, rank = cfg.num_attention_heads, cfg.kv_lora_rank
        nope, rope, vd = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, \
            cfg.v_head_dim
        hi, di, topk = cfg.index_n_heads, cfg.index_head_dim, cfg.index_topk
        width = cfg.row_width
        scale = float((nope + rope) ** -0.5)
        tables, slot, pos = batch["block_tables"], batch["token_slot"], \
            batch["token_pos"]
        t_rows, s_rows = xa.shape[0], tables.shape[0]

        def rot(v):                     # an indexer head: rope dims first
            return jnp.concatenate(
                [apply_rotary(v[..., :rope], cos, sin), v[..., rope:]], -1)

        with jax.named_scope("attn/index_k"):
            k_idx = _layer_norm(qmm(xa, ix["wk"]["kernel"], dt),
                                ix["k_norm"], cfg.index_norm_eps)
            idx_pool = idx_pool.at[batch["kv_dest"]].set(
                rot(k_idx[:, None])[:, 0].astype(idx_pool.dtype))
        with jax.named_scope("attn/index_score"):
            q_idx = rot(qmm(cq, ix["wq_b"]["kernel"], dt).reshape(
                t_rows, hi, di))
            w_idx = qmm(xa, ix["weights_proj"]["kernel"], dt).astype(F32) \
                * float(hi ** -0.5 * di ** -0.5)
        w3 = att["kv_b_proj"]["kernel"].astype(dt).reshape(
            rank, h, nope + vd)
        with jax.named_scope("attn/sparse_read"):   # absorb W_uk into q
            q_lat = jnp.einsum("thd,chd->thc", q_nope, w3[..., :nope],
                               preferred_element_type=F32).astype(dt)
            q_cat = jnp.concatenate(
                [q_lat, q_pe,
                 jnp.zeros((t_rows, h, width - rank - rope), dt)], -1)

        # the tile rows' read: the Mosaic kernel on a TPU (at widths it can
        # tile; interpreted where a test asks), else the XLA composition
        tile_read = masked_latent_read
        if self.interpret or (self.interpret is None and on_tpu()
                              and sparse_tile_read_usable(rank, width, bs)):
            tile_read = functools.partial(sparse_tile_read,
                                          interpret=bool(self.interpret))

        def read(rows, r):
            """Rows ``rows`` of the buffer in groups of ``r``."""
            n = (rows.stop - rows.start) // r
            grp = lambda a: a[rows].reshape((n, r) + a.shape[1:])
            g_pos = grp(pos)
            g_tab = tables[grp(slot)[:, 0]]
            with jax.named_scope("attn/index_score"):
                scores = index_scores(grp(q_idx), grp(w_idx), idx_pool,
                                      g_tab, g_pos, block_size=bs)
            c = scores.shape[-1]
            if r == 1:
                with jax.named_scope("attn/index_topk"):
                    sel = select_topk(scores[:, 0], topk)
                with jax.named_scope("attn/sparse_read"):
                    o_lat = gathered_latent_read(
                        grp(q_cat)[:, 0], pool, g_tab, g_pos[:, 0], sel,
                        block_size=bs, rank=rank, scale=scale)
            else:
                with jax.named_scope("attn/index_topk"):
                    key = sort_key(scores)
                    thr, cut = select_threshold(key.reshape(n * r, c), topk,
                                                live=jnp.max(g_pos) + 1)
                with jax.named_scope("attn/sparse_read"):
                    o_lat = tile_read(
                        grp(q_cat), pool, g_tab, g_pos, key,
                        thr.reshape(n, r), cut.reshape(n, r),
                        block_size=bs, rank=rank, scale=scale)
            with jax.named_scope("attn/sparse_read"):
                return jnp.einsum(
                    "thc,chd->thd",
                    o_lat.reshape(n * r, h, rank).astype(dt), w3[..., nope:],
                    preferred_element_type=F32).astype(dt)

        if decode or not prefill_tile:
            return read(slice(0, t_rows), 1), idx_pool
        out = read(slice(0, s_rows), 1)
        if t_rows > s_rows:                 # the tile segment
            out = jnp.concatenate(
                [out, read(slice(s_rows, t_rows), int(prefill_tile))])
        return out, idx_pool
