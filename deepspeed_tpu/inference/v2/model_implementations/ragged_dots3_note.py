"""Ragged dots3-note forward for the FastGen engine (``model_type:
dots3_note``; dots3-note-prev's language model is the configuration served):
latent attention of TWO geometries mixed by ``layer_types``, a gate a head on
both, the DeepSeek-V3 family's router and experts.

With ``x_n = RMSNorm(x)`` a block is ``x += Attn(x_n)``, ``x += FFN(RMSNorm(x))``.

* **A full layer** (``full_attention``) is :class:`RaggedDeepseekV3`'s mixer
  with a learned sparse-attention indexer, to the letter (``_mla`` and
  ``_sparse_read`` of the base class, which this class calls): 128 heads, query
  rank 1,024, latent rank 512, ``nope`` 128, ``rope`` 64, ``v`` 128, rotary
  base ``rope_theta``; the row ``[c | k_pe | 0]`` (640 lanes) and the indexer
  key (128) in the GLOBAL pool (``kv_row``); every query row reads the exact
  top ``index_topk`` cached positions.
* **A sliding layer** (``sliding_attention``; the ``swa_*`` keys) is the same
  mixer at another geometry and behind a window: 64 heads, both ranks 1,024,
  ``nope`` 192, ``rope`` 64, ``v`` 128, rotary base ``swa_rope_theta``; query
  ``t`` sees key ``j`` iff ``t - sliding_window_size < j <= t``.  **Its cached
  row is a latent of its own width**, ``[c (1,024) | k_pe (64) | 0]`` in 1,152
  lanes, in the WINDOW pool: ``kv_groups["window"]`` states the group's own
  row beside its layers and its width in tokens, so the state manager keeps
  two pools with two row widths behind two block tables a sequence
  (``ragged/kv_cache.py``: a row a group), and the window pool holds each
  sequence's band and no more.  The read is ABSORBED on both segments
  (``_WindowLatent._latent_read``): one-token rows take the banded walk
  (``latent_decode_attention(window=...)``: it starts at the block of ``t -
  window + 1``), the tile segment a composition over each tile's band of
  blocks, the ``(window + tile - 3) // block_size + 2`` table entries its
  rows can see.  A tile of 128 rows has a band of 640 keys, so absorbing
  (``H x (row + rank) x 2`` FLOP a pair) costs what expanding the band once a
  TILE would (33 against 37 GFLOP a tile at the published widths) and keeps
  no per-head keys; expanding once a CHUNK (``latent_expand`` over the band,
  then ``latent_prefill_attention`` behind the window's mask) would cost 2.5
  x less and needs both kernels at a head of 192 + 64, which they cannot
  tile yet (``latent_kernels_usable``; PERF.md section 7).
* **Scaled latents** (``apply_mla_qkv_lora_rescale``, read as LongCat-Flash's
  ``mla_scale_q_lora`` / ``mla_scale_kv_lora``): the query times ``sqrt(hidden
  / q_rank)``, the normalised latent times ``sqrt(hidden / kv_rank)`` before
  it is cached, each with its own layer kind's ranks (``q_scale`` /
  ``kv_scale``, which ``_mla`` applies).  The indexer's queries come from the
  unscaled query latent: a positive factor on every index score of a row
  changes no order.
* **A gate a head** (``attention_gate_type`` / ``swa_attention_gate_type``:
  ``headwise``): ``g = sigmoid(x_n W_g)``, one scalar a head, times the
  read's output before ``o_proj`` (``_mla`` reads it from the layer's own
  parameters: ``self_attn/gate_proj``).  Any other type is refused by name.
* **FFN**: the base class's, read from each layer's parameters: layer 0 a
  dense SwiGLU, every other the sigmoid router with a selection bias over
  all ``n_routed_experts`` (top-k renormalised, one group) plus the ungated
  shared expert; the layer holds ``held_experts`` from ``expert_start``.

Device scopes under ``layers_<i>``: a full layer keeps the base class's
(``attn/q_proj``, ``attn/kv_latent``, ``attn/index_k``, ``attn/index_score``,
``attn/index_topk``, ``attn/sparse_read``, ``attn/gate``, ``attn/out_proj``);
a sliding layer ``attn/q_proj``, ``attn/kv_latent``, ``attn/window_read``
(one-token rows: absorb, the banded walk, ``W_uv``), ``attn/window_prefill``
(the tile segment), ``attn/gate``, ``attn/out_proj``; then ``mlp`` or the
``moe/*`` scopes.  The rotary dims are in the rotate-half layout
(``checkpoint/hf_loader.py`` de-interleaves the published ones).
"""

from __future__ import annotations

import dataclasses
import types
from typing import Any, Dict, Optional, Sequence

import jax
import jax.numpy as jnp

from deepspeed_tpu.inference.v2.kernels.latent_flash import (
    latent_decode_attention, latent_row_width, latent_walk_usable)
from deepspeed_tpu.inference.v2.model_implementations.ragged_deepseek_v3 \
    import RaggedDeepseekV3
from deepspeed_tpu.inference.v2.modules.attention import _rotary
from deepspeed_tpu.utils.platform import on_tpu

F32 = jnp.float32
KINDS = ("sliding_attention", "full_attention")


@dataclasses.dataclass
class Dots3NoteConfig:
    vocab_size: int = 152064
    hidden_size: int = 5120
    intermediate_size: int = 13824
    moe_intermediate_size: int = 1536
    num_hidden_layers: int = 46
    #: "sliding_attention" | "full_attention" a layer; None: the published
    #: pattern (full at layer 0 and at every layer ``i % 4 == 1``)
    layer_types: Optional[Sequence[str]] = None
    # -- a full layer's mixer, under the names the base class reads ------ #
    num_attention_heads: int = 128
    kv_lora_rank: int = 512
    q_lora_rank: int = 1024
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    rope_theta: float = 8e7
    index_n_heads: int = 64
    index_head_dim: int = 128
    index_topk: int = 2048
    index_norm_eps: float = 1e-6
    attention_gate_type: str = "headwise"
    # -- a sliding layer's ----------------------------------------------- #
    swa_num_attention_heads: int = 64
    swa_kv_lora_rank: int = 1024
    swa_q_lora_rank: int = 1024
    swa_qk_nope_head_dim: int = 192
    swa_qk_rope_head_dim: int = 64
    swa_v_head_dim: int = 128
    swa_rope_theta: float = 5e4
    swa_attention_gate_type: str = "headwise"
    sliding_window_size: int = 513
    #: query and latent times sqrt(hidden / their rank): the module doc
    apply_mla_qkv_lora_rescale: bool = True
    # -- FFN (the base class's names) ------------------------------------ #
    n_routed_experts: int = 256
    n_shared_experts: int = 1
    num_experts_per_tok: int = 8
    first_k_dense_replace: int = 1
    moe_layer_freq: int = 1
    n_group: int = 1
    topk_group: int = 1
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    scoring_func: str = "sigmoid"
    topk_method: str = "noaux_tc"
    rms_norm_eps: float = 1e-5
    #: the latent norms' eps: the DeepSeek-V3 modelling code's default
    latent_norm_eps: float = 1e-6
    max_position_embeddings: int = 524288
    #: the experts this program holds: ``[expert_start, expert_start +
    #: held_experts)`` of the router's; None = all of them
    held_experts: Optional[int] = None
    expert_start: int = 0
    dtype: Any = jnp.bfloat16

    def __post_init__(self):
        if self.layer_types is None:
            self.layer_types = [KINDS[i == 0 or i % 4 == 1]
                                for i in range(self.num_hidden_layers)]
        self.layer_types = list(self.layer_types)
        if len(self.layer_types) != self.num_hidden_layers \
                or set(self.layer_types) - set(KINDS):
            raise ValueError(
                f"layer_types: {self.num_hidden_layers} entries of "
                f"{' | '.join(KINDS)} wanted, got {self.layer_types}")
        for key in ("attention_gate_type", "swa_attention_gate_type"):
            if getattr(self, key) != "headwise":
                raise NotImplementedError(
                    f"{key}={getattr(self, key)!r}: only the headwise gate "
                    f"(one sigmoid scalar a head, from the normed input) is "
                    f"implemented")
        if self.n_group != 1 or self.topk_group != 1 \
                or self.scoring_func != "sigmoid" \
                or self.topk_method != "noaux_tc":
            raise NotImplementedError(
                f"n_group={self.n_group}, topk_group={self.topk_group}, "
                f"scoring_func={self.scoring_func!r}, topk_method="
                f"{self.topk_method!r}: only the sigmoid score with a "
                f"selection bias over one group is implemented")
        if self.index_head_dim % 128 \
                or self.qk_rope_head_dim > self.index_head_dim:
            raise NotImplementedError(
                f"index_head_dim={self.index_head_dim}: the indexer key is "
                f"a pool row of whole 128-lane tiles whose first "
                f"qk_rope_head_dim ({self.qk_rope_head_dim}) values rotate")

    def is_window(self, i: int) -> bool:
        return self.layer_types[i] == "sliding_attention"

    def is_moe(self, i: int) -> bool:
        return i >= self.first_k_dense_replace \
            and i % self.moe_layer_freq == 0

    def _scale(self, rank: int) -> float:
        return (self.hidden_size / rank) ** 0.5 \
            if self.apply_mla_qkv_lora_rescale else 1.0

    @property
    def q_scale(self) -> float:
        return self._scale(self.q_lora_rank)

    @property
    def kv_scale(self) -> float:
        return self._scale(self.kv_lora_rank)

    @property
    def row_width(self) -> int:
        return latent_row_width(self.kv_lora_rank, self.qk_rope_head_dim)

    @property
    def swa(self):
        """A sliding layer's geometry under the names the mixer reads (no
        indexer: the window is its selection)."""
        rank, rope = self.swa_kv_lora_rank, self.swa_qk_rope_head_dim
        return types.SimpleNamespace(
            hidden_size=self.hidden_size,
            num_attention_heads=self.swa_num_attention_heads,
            kv_lora_rank=rank, q_lora_rank=self.swa_q_lora_rank,
            qk_nope_head_dim=self.swa_qk_nope_head_dim,
            qk_rope_head_dim=rope, v_head_dim=self.swa_v_head_dim,
            rope_theta=self.swa_rope_theta,
            q_scale=self._scale(self.swa_q_lora_rank),
            kv_scale=self._scale(rank), index_topk=None,
            row_width=latent_row_width(rank, rope),
            rms_norm_eps=self.rms_norm_eps,
            latent_norm_eps=self.latent_norm_eps, dtype=self.dtype)


def param_shapes(cfg: Dots3NoteConfig) -> Dict[str, Any]:
    """The parameter tree :class:`RaggedDots3Note` reads, as shapes: the
    DeepSeek-V3 family's (every matrix [in, out]; ``kv_b_proj`` columns per
    head ``k_nope | v``; an indexer head's rotated dims first) with each
    layer's attention at its own kind's widths, ``gate_proj`` (hidden ->
    heads) on both kinds and the indexer on full layers alone."""
    dt, h = cfg.dtype, cfg.hidden_size
    e = cfg.held_experts or cfg.n_routed_experts
    f = cfg.moe_intermediate_size
    sds = lambda *shape: jax.ShapeDtypeStruct(shape, dt)
    kern = lambda i, o: {"kernel": sds(i, o)}
    swiglu = lambda width: {"gate_proj": kern(h, width),
                            "up_proj": kern(h, width),
                            "down_proj": kern(width, h)}

    def attention(g, indexer):
        hq, qr, rank = g.num_attention_heads, g.q_lora_rank, g.kv_lora_rank
        qk = g.qk_nope_head_dim + g.qk_rope_head_dim
        att = {
            "q_a_proj": kern(h, qr), "q_a_layernorm": {"scale": sds(qr)},
            "q_b_proj": kern(qr, hq * qk),
            "kv_a_proj_with_mqa": kern(h, rank + g.qk_rope_head_dim),
            "kv_a_layernorm": {"scale": sds(rank)},
            "kv_b_proj": kern(rank, hq * (g.qk_nope_head_dim
                                          + g.v_head_dim)),
            "gate_proj": kern(h, hq),
            "o_proj": kern(hq * g.v_head_dim, h)}
        if indexer:
            hi, di = cfg.index_n_heads, cfg.index_head_dim
            att["indexer"] = {
                "wq_b": kern(qr, hi * di), "wk": kern(h, di),
                "k_norm": {"scale": sds(di), "bias": sds(di)},
                "weights_proj": kern(h, hi)}
        return att

    def layer(i):
        mlp = swiglu(cfg.intermediate_size) if not cfg.is_moe(i) else {
            "gate": {"wg": kern(h, cfg.n_routed_experts),
                     "e_score_correction_bias": sds(cfg.n_routed_experts)},
            "experts": {"w_gate": sds(e, h, f), "w_up": sds(e, h, f),
                        "w_down": sds(e, f, h)},
            "shared_expert": swiglu(cfg.n_shared_experts * f)}
        window = cfg.is_window(i)
        return {"input_layernorm": {"scale": sds(h)},
                "post_attention_layernorm": {"scale": sds(h)},
                "self_attn": attention(cfg.swa if window else cfg,
                                       not window),
                "mlp": mlp}

    return {"embed_tokens": {"embedding": sds(cfg.vocab_size, h)},
            **{f"layers_{i}": layer(i)
               for i in range(cfg.num_hidden_layers)},
            "norm": {"scale": sds(h)},
            "lm_head": kern(h, cfg.vocab_size)}


def banded_absorbed_read_xla(q_cat, pool, tables, pos, block_size, rank,
                             scale, window):
    """The absorbed form over a BAND, as an XLA composition: ``q_cat [n, r,
    H, W]`` in groups of ``r`` rows that share a table (``tables [n, B]``;
    a one-token row: r = 1; a tile: its rows at consecutive positions from
    ``pos[:, 0]``, pad rows at -1 behind them), row ``(i, a)`` against the
    keys ``j`` of its sequence with ``pos - window < j <= pos``.  Only the
    ``(window + r - 3) // block_size + 2`` table entries a group's rows can
    see are gathered, from the block of its first row's ``pos - window +
    1``: the entries below (released: the trash block) are never read.
    Returns ``sum p c`` [n, r, H, rank]; a pad row comes out finite."""
    n, r = pos.shape
    b = tables.shape[1]
    nb = min(b, (window + r - 3) // block_size + 2)
    lo = jnp.maximum(pos[:, 0] - window + 1, 0) // block_size       # [n]
    entry = lo[:, None] + jnp.arange(nb, dtype=jnp.int32)[None, :]
    blocks = jnp.take_along_axis(tables, jnp.minimum(entry, b - 1), axis=1)
    inside = jnp.arange(block_size, dtype=jnp.int32)
    ctx = pool[(blocks[:, :, None] * block_size + inside).reshape(n, -1)]
    # an entry past the table's end repeats its last one: its keys stand
    # past every position of the group and leave by the mask
    key = (entry[:, :, None] * block_size + inside).reshape(n, 1, -1)
    keep = jnp.logical_and(key <= pos[:, :, None],
                           key > pos[:, :, None] - window)
    scores = jnp.einsum("nrhw,ncw->nrhc", q_cat, ctx,
                        preferred_element_type=F32) * scale
    probs = jax.nn.softmax(jnp.where(keep[:, :, None, :], scores, -1e30), -1)
    return jnp.einsum("nrhc,ncv->nrhv", probs.astype(ctx.dtype),
                      ctx[..., :rank],
                      preferred_element_type=F32).astype(q_cat.dtype)


class _WindowLatent(RaggedDeepseekV3):
    """The latent mixer of a sliding layer: the base class's ``_mla`` at the
    sliding geometry (``Dots3NoteConfig.swa``), its read behind a window.
    The batch it is handed names the WINDOW group's table and write targets
    (``block_tables_win`` / ``kv_dest_win``) under the names every read
    knows."""

    def __init__(self, config, block_size: int, window: int):
        super().__init__(config, block_size)
        self.window = int(window)

    def _latent_read(self, att, q_nope, q_pe, pool, batch, prefill_tile,
                     decode):
        """``out [T, H, v_head_dim]`` of every row over its band, absorbed:
        one-token rows by the banded walk (a TPU, or a test's interpret
        mode) or the composition with groups of one, the tile segment by
        the composition a tile."""
        cfg, dt, bs = self.config, self.config.dtype, self.block_size
        h, rank = cfg.num_attention_heads, cfg.kv_lora_rank
        nope, rope, vd = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, \
            cfg.v_head_dim
        width, window = cfg.row_width, self.window
        scale = float((nope + rope) ** -0.5)
        tables, slot, pos = batch["block_tables"], batch["token_slot"], \
            batch["token_pos"]
        t_rows, s_rows = q_nope.shape[0], tables.shape[0]
        w3 = att["kv_b_proj"]["kernel"].astype(dt).reshape(
            rank, h, nope + vd)
        walk = self.interpret
        if walk is None:
            walk = on_tpu() and latent_walk_usable(rank, bs)

        def read(rows, r, scope):
            """Rows ``rows`` of the buffer in groups of ``r``."""
            with jax.named_scope(scope):
                q_lat = jnp.einsum("thd,chd->thc", q_nope[rows],
                                   w3[..., :nope],
                                   preferred_element_type=F32).astype(dt)
                t = q_lat.shape[0]
                q_cat = jnp.concatenate(
                    [q_lat, q_pe[rows],
                     jnp.zeros((t, h, width - rank - rope), dt)], -1)
                if r == 1 and walk:
                    o_lat = latent_decode_attention(
                        q_cat, pool, tables, slot[rows], pos[rows],
                        block_size=bs, value_dim=rank, scale=scale,
                        window=window, interpret=bool(self.interpret))
                else:
                    n = t // r
                    o_lat = banded_absorbed_read_xla(
                        q_cat.reshape(n, r, h, width), pool,
                        tables[slot[rows].reshape(n, r)[:, 0]],
                        pos[rows].reshape(n, r), bs, rank, scale,
                        window).reshape(t, h, rank)
                return jnp.einsum("thc,chd->thd", o_lat, w3[..., nope:],
                                  preferred_element_type=F32).astype(dt)

        if decode or not prefill_tile:
            return read(slice(0, t_rows), 1, "attn/window_read")
        out = read(slice(0, s_rows), 1, "attn/window_read")
        if t_rows > s_rows:                 # the tile segment
            out = jnp.concatenate(
                [out, read(slice(s_rows, t_rows), int(prefill_tile),
                           "attn/window_prefill")])
        return out


class RaggedDots3Note(RaggedDeepseekV3):
    """Callable ragged forward bound to a :class:`Dots3NoteConfig`.  The
    base class's members (``kv_row``, ``index_topk``, ``_mla``,
    ``_sparse_read``, the FFN of ``__call__``'s loop) serve the full layers
    and the global pool; ``kv_groups`` states the sliding layers' pool."""

    def __init__(self, config: Dots3NoteConfig, block_size: int, mesh=None):
        # (first: the base class's constructor sets ``interpret``)
        self._swa = _WindowLatent(config.swa, block_size,
                                  config.sliding_window_size)
        super().__init__(config, block_size, mesh=mesh)

    @property
    def interpret(self):
        return self._swa.interpret

    @interpret.setter
    def interpret(self, value):     # one switch for both kinds' kernels
        self._swa.interpret = value

    @property
    def kv_groups(self) -> Dict[str, Dict[str, Any]]:
        """The sliding layers: a pool and a block table a sequence of their
        own, which hold the last ``window`` positions only, at the group's
        OWN row (``row``: leaf -> lanes; the global layers keep
        ``kv_row``)."""
        cfg = self.config
        return {"window": {
            "layers": [i for i in range(cfg.num_hidden_layers)
                       if cfg.is_window(i)],
            "window": int(cfg.sliding_window_size),
            "row": {"ckv": cfg.swa.row_width}}}

    @staticmethod
    def _window_view(batch):
        """The sliding layers' view of the batch: their group's table and
        write targets under the names every read knows."""
        return {**batch, "block_tables": batch["block_tables_win"],
                "kv_dest": batch["kv_dest_win"]}

    def __call__(self, params: Dict[str, Any], cache: Dict[str, Any],
                 batch: Dict[str, jax.Array], prefill_tile=None,
                 decode=False):
        """Returns ``(logits [S, vocab], new cache)``.  ``batch`` carries
        ``block_tables_win`` and ``kv_dest_win`` beside the usual fields."""
        cfg = self.config
        with jax.named_scope("embed"):
            x = params["embed_tokens"]["embedding"].astype(cfg.dtype)[
                batch["token_ids"]]
        pos = batch["token_pos"]
        rot = {False: _rotary(pos, cfg.qk_rope_head_dim, cfg.rope_theta),
               True: _rotary(pos, cfg.swa_qk_rope_head_dim,
                             cfg.swa_rope_theta)}
        win = self._window_view(batch)
        new_cache = {}
        for i in range(cfg.num_hidden_layers):
            lp = params[f"layers_{i}"]
            window = cfg.is_window(i)
            with jax.named_scope(f"layers_{i}"):
                mixer = self._swa if window else self
                out, new_cache[f"layer_{i}"] = mixer._mla(
                    lp, x, cache[f"layer_{i}"], win if window else batch,
                    *rot[window], prefill_tile, decode)
                x = self._ffn(lp, x + out)
        return self._head(params, x, batch), new_cache
