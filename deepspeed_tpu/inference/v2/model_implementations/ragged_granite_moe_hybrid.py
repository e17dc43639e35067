"""Ragged Granite-4.0-H forward for the FastGen engine (``model_type:
granitemoehybrid``): Mamba-2 (state-space duality) mixers in nine layers of
ten, grouped-query softmax attention WITHOUT any positional embedding in the
tenth (``layer_types`` read as given), a routed-expert FFN with a shared
expert in EVERY layer, the head tied to the embedding, and four scalar
multipliers on the embedding, the residual branches, the attention scores
and the logits::

    h0 = embedding_multiplier * E[ids]
    h  = h + residual_multiplier * mixer(RMSNorm(h))
    h  = h + residual_multiplier * (moe(u) + shared(u)),   u = RMSNorm(h)
    logits = (RMSNorm(h_L) E^T) / logits_scaling

What is new beside :class:`RaggedJamba` (Mamba-1), whose call signature,
slot pool and two-segment contract this model shares:

* **The Mamba-2 mixer** (``H = mamba_n_heads`` heads of ``P =
  mamba_d_head`` channels, ``Di = H P``; ``N = mamba_d_state``; ONE group:
  ``B`` and ``C`` shared by all heads; ``K = mamba_d_conv`` taps)::

      [z | xBC | dt_raw] = norm(h) W_in          widths Di | Di + 2 N | H
      xBC_t = silu(b_c + sum_j w_c[j] * xBC_{t-K+1+j})   (depthwise, causal)
      [X | B | C] = xBC                           B and C INSIDE the convolution
      dt = softplus(dt_raw + dt_bias) [H];  A = -exp(A_log) [H]: ONE decay a head
      S_h = exp(dt_h A_h) S_h + B^T (dt_h X_h);  Y_h = C S_h + D_h X_h
      y = RMSNorm_g(Y * silu(z))      the gate FIRST, then one norm over all Di
      out = y W_out

  Per sequence a layer keeps ``S`` (float32, ``[N, Di]``: the channels on
  the lanes, a head's ``P`` side by side) and the last ``K - 1`` inputs of
  the convolution (the model's dtype, one flat row ``[(K - 1) (Di + 2 N)]``)
  in a slot of the state manager's pool: ``state_spec`` has the leaves
  ``ssm`` and ``conv``, 4,244,992 B a layer and sequence at the published
  sizes (128 x 8192 x 4 + 3 x 8448 x 2) against 4,096 B a token of keys and
  values in the attention layer.
* **The recurrence** is ``ops/ssd.py``: ``ssd_step`` for the one-token rows
  (the decay a ROW ``[Di]`` a token: no ``[N, Di]`` copy of ``A``),
  ``ssd_chunk`` for the tile segment in the matmul form (a tile a chunk,
  the state carried from tile to tile in the pool).  What stays with XLA
  around the kernels: the softplus and ``dt x`` (scope ``mamba2/scan``),
  ``+ D x``, the gate and the gated norm (``mamba2/out``).
* **The attention scale is ``attention_multiplier``, not ``1/sqrt(d)``.**
  The shared ``ragged_attention_block`` and every kernel it calls keep
  their ``1/sqrt(d)``; the block multiplies ``q`` by ``cfg.query_scale =
  attention_multiplier * sqrt(d)`` right after the projection (a static
  branch on the config: a model without the attribute keeps its program to
  the letter), so the scores are ``q . k * attention_multiplier``.
* **Routed experts beside state-space layers**, a share of them: the router
  scores all ``num_local_experts``, takes the top-k of the LOGITS and a
  softmax over those k (= ``dropless_moe``'s softmax-then-top-k,
  renormalised); the layer holds ``held_experts`` from ``expert_start`` and
  adds their part plus the shared expert.  Nothing stands in for the chips
  that hold the rest.

Static branches are on a layer's own parameters: one with ``mamba`` is a
Mamba-2 layer.  Decode steps and two-segment (tiled) batches only, as every
model with state slots; ``tp = 1``.  Refused by name: more than one group
(``mamba_n_groups``), a positional embedding other than ``nope``, a bias in
the projections, ``mamba_n_heads x mamba_d_head`` other than ``mamba_expand
x hidden_size``, a ``layer_types`` entry that is neither kind, an untied
head, a mesh with a ``model`` axis.

Layout (what ``checkpoint/hf_loader.py`` produces): every matrix [in, out];
``mamba/in_proj`` columns ``z | xBC | dt``; ``mamba/conv1d/kernel`` [taps,
channels] over ``X | B | C`` with the last tap on the current token,
``conv1d/bias`` [channels]; ``mamba/dt_bias``, ``A_log``, ``D`` [H];
``mamba/norm/scale`` [Di]; ``block_sparse_moe`` as ``dropless_moe`` reads
it (``gate/wg``, ``experts/{w_gate, w_up, w_down}``, ``shared_expert``).
Device scopes under ``layers_<i>``: ``mamba2/in_proj`` (norm and ``W_in``),
``mamba2/conv``, ``mamba2/scan``, ``mamba2/out`` (``+ D x``, the gate, the
gated norm, ``W_out``); ``attn/*`` as RaggedLlama; ``moe/router``,
``moe/dispatch``, ``moe/experts``, ``moe/combine``, ``moe/shared``.
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
from deepspeed_tpu.inference.v2.modules.conv import _causal_conv, _silu
from deepspeed_tpu.inference.v2.modules.moe import dropless_moe
from deepspeed_tpu.inference.v2.ragged.kv_cache import CacheLayoutError
from deepspeed_tpu.ops.quantized_matmul import qmm
from deepspeed_tpu.ops.ssd import head_lanes, ssd_chunk, ssd_step

F32 = jnp.float32
LAYER_KINDS = ("mamba", "attention")


@dataclasses.dataclass
class GraniteMoeHybridConfig:
    vocab_size: int = 100352
    hidden_size: int = 4096
    #: the width of ONE routed expert
    intermediate_size: int = 768
    shared_intermediate_size: int = 1536
    num_hidden_layers: int = 40
    #: one kind a layer; None: ``attention`` where ``l % 10 == 5``
    layer_types: Optional[Tuple[str, ...]] = None
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    mamba_n_heads: int = 128
    mamba_d_head: int = 64
    mamba_d_state: int = 128
    mamba_n_groups: int = 1
    mamba_d_conv: int = 4
    mamba_expand: int = 2
    mamba_conv_bias: bool = True
    mamba_proj_bias: bool = False
    attention_bias: bool = False
    position_embedding_type: str = "nope"
    #: the router's width (every expert of the model)
    num_local_experts: int = 72
    num_experts_per_tok: int = 10
    #: the experts this program holds: ``[expert_start, expert_start +
    #: held_experts)`` of the router's; None = all of them
    held_experts: Optional[int] = None
    expert_start: int = 0
    embedding_multiplier: float = 12.0
    residual_multiplier: float = 0.22
    attention_multiplier: float = 0.0078125
    logits_scaling: float = 16.0
    rms_norm_eps: float = 1e-5
    max_position_embeddings: int = 131072
    tie_word_embeddings: bool = True
    dtype: Any = jnp.bfloat16
    # read by the shared attention block
    sliding_window: Optional[int] = None

    def __post_init__(self):
        if self.layer_types is None:
            self.layer_types = tuple(
                LAYER_KINDS[l % 10 == 5]
                for l in range(self.num_hidden_layers))
        self.layer_types = tuple(self.layer_types)
        for l, kind in enumerate(self.layer_types):
            if kind not in LAYER_KINDS:
                raise NotImplementedError(
                    f"layer_types[{l}] = {kind!r}: a granitemoehybrid layer "
                    f"is one of {LAYER_KINDS}")
        if len(self.layer_types) != self.num_hidden_layers:
            raise ValueError(
                f"layer_types names {len(self.layer_types)} layers, "
                f"num_hidden_layers is {self.num_hidden_layers}")
        if self.mamba_n_groups != 1:
            raise NotImplementedError(
                f"mamba_n_groups={self.mamba_n_groups}: B and C shared by "
                f"all heads (one group) is what ops/ssd.py computes")
        if self.position_embedding_type != "nope":
            raise NotImplementedError(
                f"position_embedding_type={self.position_embedding_type!r}: "
                f"the attention layers carry no positional embedding "
                f"('nope'); a rotary variant is not implemented")
        if self.mamba_proj_bias or self.attention_bias \
                or not self.tie_word_embeddings:
            raise NotImplementedError(
                f"mamba_proj_bias={self.mamba_proj_bias}, attention_bias="
                f"{self.attention_bias}, tie_word_embeddings="
                f"{self.tie_word_embeddings}: the projections carry no "
                f"bias and the head is the embedding")
        if self.mamba_n_heads * self.mamba_d_head != self.d_inner:
            raise ValueError(
                f"mamba_n_heads x mamba_d_head = {self.mamba_n_heads} x "
                f"{self.mamba_d_head} is not mamba_expand x hidden_size = "
                f"{self.d_inner}")

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def d_inner(self) -> int:
        return self.mamba_expand * self.hidden_size

    @property
    def conv_dim(self) -> int:
        return self.d_inner + 2 * self.mamba_n_groups * self.mamba_d_state

    @property
    def query_scale(self) -> float:
        """What ``ragged_attention_block`` multiplies ``q`` by so that its
        ``1/sqrt(d)`` reads ``attention_multiplier``."""
        return float(self.attention_multiplier) * self.head_dim ** 0.5

    def is_attention(self, i: int) -> bool:
        return self.layer_types[i] == "attention"


def _gated_norm(y, z, scale, eps):
    """Mamba-2's output norm: the gate FIRST, then ONE RMSNorm over the
    whole inner width (one group).  ``y``, ``z`` [T, Di] float32."""
    return _rms_norm(y * _silu(z), scale, eps)


def param_shapes(cfg: GraniteMoeHybridConfig) -> Dict[str, Any]:
    """The parameter tree :class:`RaggedGraniteMoeHybrid` reads, as shapes
    (every matrix stored [in, out])."""
    dt, h = cfg.dtype, cfg.hidden_size
    hq, hkv, d = cfg.num_attention_heads, cfg.num_key_value_heads, \
        cfg.head_dim
    di, hm = cfg.d_inner, cfg.mamba_n_heads
    e = cfg.held_experts or cfg.num_local_experts
    f, fs = cfg.intermediate_size, cfg.shared_intermediate_size
    sds = lambda *shape: jax.ShapeDtypeStruct(shape, dt)
    kern = lambda i, o: {"kernel": sds(i, o)}

    def layer(i):
        mixer = {"self_attn": {
            "q_proj": kern(h, hq * d), "k_proj": kern(h, hkv * d),
            "v_proj": kern(h, hkv * d), "o_proj": kern(hq * d, h)}} \
            if cfg.is_attention(i) else {"mamba": {
                "in_proj": kern(h, di + cfg.conv_dim + hm),
                "conv1d": {"kernel": sds(cfg.mamba_d_conv, cfg.conv_dim),
                           **({"bias": sds(cfg.conv_dim)}
                              if cfg.mamba_conv_bias else {})},
                "dt_bias": sds(hm), "A_log": sds(hm), "D": sds(hm),
                "norm": {"scale": sds(di)},
                "out_proj": kern(di, h)}}
        return {"input_layernorm": {"scale": sds(h)},
                "post_attention_layernorm": {"scale": sds(h)}, **mixer,
                "block_sparse_moe": {
                    "gate": {"wg": kern(h, cfg.num_local_experts)},
                    "experts": {"w_gate": sds(e, h, f), "w_up": sds(e, h, f),
                                "w_down": sds(e, f, h)},
                    "shared_expert": {"gate_proj": kern(h, fs),
                                      "up_proj": kern(h, fs),
                                      "down_proj": kern(fs, h)}}}

    return {"embed_tokens": {"embedding": sds(cfg.vocab_size, h)},
            **{f"layers_{i}": layer(i)
               for i in range(cfg.num_hidden_layers)},
            "norm": {"scale": sds(h)}}


class RaggedGraniteMoeHybrid:
    """Callable ragged forward bound to a :class:`GraniteMoeHybridConfig`."""

    #: the attention reads pass no scales: int8 pools are refused by the
    #: engine
    supports_quantized_kv = False

    def __init__(self, config: GraniteMoeHybridConfig, block_size: int,
                 mesh=None):
        if mesh is not None and dict(mesh.shape).get("model", 1) > 1:
            raise NotImplementedError(
                f"RaggedGraniteMoeHybrid serves tp = 1: with one group "
                f"every Mamba-2 head reads the same B and C and the gated "
                f"norm spans all of them, so a head split needs a "
                f"collective it has not got (mesh 'model' axis = "
                f"{dict(mesh.shape)['model']})")
        self.config = config
        self.block_size = block_size
        self.tp = 1
        #: None: the recurrence's Mosaic kernels on a TPU, their XLA
        #: compositions elsewhere; tests pass True (interpret mode)
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
        """The per-sequence state the engine's slot pool holds: each
        Mamba-2 layer's state (float32, the channels on the lanes) and the
        tail of its convolution (its ``K - 1`` rows of ``Di + 2 N`` back to
        back in ONE row a slot, the layout of ``modules/conv.py``)."""
        cfg = self.config
        return {
            "layers": [i for i in range(cfg.num_hidden_layers)
                       if not cfg.is_attention(i)],
            "leaves": {
                "ssm": ((cfg.mamba_d_state, cfg.d_inner), F32),
                "conv": (((cfg.mamba_d_conv - 1) * cfg.conv_dim,),
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
                "RaggedGraniteMoeHybrid runs decode steps and two-segment "
                "(tiled) batches; a batch packed back to back has no tile "
                "a sequence's state could be carried along")
        embedding = params["embed_tokens"]["embedding"].astype(dt)
        with jax.named_scope("embed"):
            x = (embedding[batch["token_ids"]].astype(F32)
                 * cfg.embedding_multiplier).astype(dt)
        h, hkv, d = (cfg.num_attention_heads, cfg.num_key_value_heads,
                     cfg.head_dim)
        rm = cfg.residual_multiplier

        def add(x, branch):         # h + residual_multiplier * branch
            return (x.astype(F32) + rm * branch.astype(F32)).astype(dt)

        new_cache = {}
        for i in range(cfg.num_hidden_layers):
            lp = params[f"layers_{i}"]
            with jax.named_scope(f"layers_{i}"):
                if "mamba" in lp:
                    out, new_cache[f"layer_{i}"] = self._mamba2(
                        lp, x, cache[f"layer_{i}"], batch, prefill_tile)
                else:
                    with jax.named_scope("attn/qkv"):
                        xa = _rms_norm(x, lp["input_layernorm"]["scale"],
                                       cfg.rms_norm_eps)
                    # no positional embedding: cos = sin = None; the block
                    # scales q by cfg.query_scale (the module doc)
                    out, new_cache[f"layer_{i}"] = ragged_attention_block(
                        lp["self_attn"], xa, cache[f"layer_{i}"], batch,
                        self.block_size, cfg, h, hkv, d, None, None,
                        prefill_tile=prefill_tile, decode_mode=decode)
                x = add(x, out)
                with jax.named_scope("moe/router"):
                    xm = _rms_norm(x, lp["post_attention_layernorm"]["scale"],
                                   cfg.rms_norm_eps)
                x = add(x, dropless_moe(
                    xm, lp["block_sparse_moe"], cfg.num_experts_per_tok, dt,
                    renormalize=True, expert_start=cfg.expert_start))
        with jax.named_scope("lm_head"):
            x = _rms_norm(x, params["norm"]["scale"], cfg.rms_norm_eps)
            x = x[batch["logits_idx"]]
            logits = ((x @ embedding.T).astype(F32)
                      / cfg.logits_scaling).astype(dt)
        return logits, new_cache

    def _mamba2(self, lp, x, layer_cache, batch, prefill_tile):
        """One Mamba-2 mixer over the flat token buffer.  Returns ``(out
        [T, hidden], {"ssm", "conv"})``."""
        cfg, mb, dt = self.config, lp["mamba"], self.config.dtype
        di, n, eps = cfg.d_inner, cfg.mamba_d_state, cfg.rms_norm_eps
        pool = layer_cache["ssm"]
        scratch = pool.shape[0] - 1
        pos, sslot = batch["token_pos"], batch["state_slot"]
        t_rows, s_rows = x.shape[0], sslot.shape[0]
        with jax.named_scope("mamba2/in_proj"):
            xn = _rms_norm(x, lp["input_layernorm"]["scale"], eps)
            proj = qmm(xn, mb["in_proj"]["kernel"], dt)
            z, xbc, dt_raw = (proj[:, :di], proj[:, di:di + cfg.conv_dim],
                              proj[:, di + cfg.conv_dim:])
        with jax.named_scope("mamba2/conv"):
            xbc, conv = _causal_conv(xbc, mb["conv1d"]["kernel"],
                                     layer_cache["conv"], batch,
                                     bias=mb["conv1d"].get("bias"),
                                     prefill_tile=prefill_tile)
        with jax.named_scope("mamba2/scan"):
            u32 = xbc[:, :di].astype(F32)
            b = xbc[:, di:di + n].astype(F32)
            c = xbc[:, di + n:].astype(F32)
            step = jax.nn.softplus(dt_raw.astype(F32)
                                   + mb["dt_bias"].astype(F32))     # [T, H]
            # pad rows: decay 1, input 0
            step = jnp.where((pos >= 0)[:, None], step, 0.0)
            da = step * -jnp.exp(mb["A_log"].astype(F32))
            dtx = head_lanes(step, di) * u32
            rows = slice(0, s_rows)             # one token a row
            row_slot = jnp.where(pos[rows] >= 0,
                                 sslot[batch["token_slot"][rows]], scratch)
            y, pool = ssd_step(pool, da[rows], dtx[rows], b[rows], c[rows],
                               row_slot, pos[rows] == 0,
                               interpret=self.interpret)
            if t_rows > s_rows:                 # the tile segment
                rows = slice(s_rows, t_rows)
                first = slice(s_rows, t_rows, int(prefill_tile))
                tile_slot = jnp.where(pos[first] >= 0,
                                      sslot[batch["token_slot"][first]],
                                      scratch)
                y2, pool = ssd_chunk(pool, da[rows], dtx[rows], b[rows],
                                     c[rows], tile_slot, pos[first] == 0,
                                     int(prefill_tile),
                                     interpret=self.interpret)
                y = jnp.concatenate([y, y2])
        with jax.named_scope("mamba2/out"):
            y = _gated_norm(
                y + head_lanes(mb["D"].astype(F32)[None, :], di) * u32,
                z.astype(F32), mb["norm"]["scale"], eps)
            out = qmm(y.astype(dt), mb["out_proj"]["kernel"], dt)
        return out, {"ssm": pool, "conv": conv}
