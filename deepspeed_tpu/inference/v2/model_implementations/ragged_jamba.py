"""Ragged Jamba forward for the FastGen engine (``model_type: jamba``,
dense: ``num_experts`` 1): Mamba (state-space) mixers in thirteen layers of
fourteen, grouped-query softmax attention WITHOUT any positional embedding
in the fourteenth (``l % attn_layer_period == attn_layer_offset``), a dense
SwiGLU in every layer, the head tied to the embedding.

What is new beside :class:`RaggedQwen3Next` and :class:`RaggedLfm2`, whose
call signature and slot pool this model shares:

* **The Mamba mixer** (``mamba_expand`` x hidden = ``Di`` channels,
  ``mamba_d_state`` N, ``mamba_dt_rank`` R, ``mamba_d_conv`` K taps)::

      [x | z] = norm(h) W_in
      x_t = silu(b_c + sum_j w_c[j] * x_{t-K+1+j})     (depthwise, causal)
      [dt_r | B | C] = x W_x;  each RMSNormed (Jamba's inner norms)
      dt = softplus(dt_r W_dt + b_dt);  A = -exp(A_log)
      s_t = exp(dt_t A) * s_{t-1} + (dt_t x_t) B_t;  y_t = s_t C_t + D x_t
      out = (y * silu(z)) W_out

  Per sequence a layer keeps ``s`` (float32, ``[N, Di]``: the channels on
  the lanes) and the last ``K - 1`` convolution inputs (the model's dtype,
  one flat row ``[(K - 1) Di]``)
  in a slot of the state manager's pool (``ragged/state_pool.py``):
  ``state_spec`` has the leaves ``ssm`` and ``conv``, 358,400 B a layer and
  sequence at the published sizes against 512 B a token of keys and values
  in an attention layer.  The state, not the keys, is this model's cache.
* **The selective scan** is ``ops/selective_scan.py``: ``ssm_step`` for the
  one-token rows, ``ssm_chunk`` for the tile segment, the time loop inside
  the kernel, so no ``[T, N, Di]`` tensor stands in HBM.  What stays with
  XLA, fused into the projections around the kernels: the inner norms, the
  softplus and ``dt * x`` (scope ``mamba/x_proj``), ``+ D x`` and ``*
  silu(z)`` (``mamba/out``).
* **The convolution has a bias** (``mamba_conv_bias``):
  ``modules/conv.py::_causal_conv(..., bias=)``, the function the two
  other stateful families call: the one-token rows, 256 of them in a
  decode step, read and write their slots' tails by one-hot matmuls there
  (the form this family met first, its own ``_conv`` until PR 57), the
  tile segment goes through the chunk form an entry a tile.
* **Attention without positions**: ``ragged_attention_block`` with ``cos =
  sin = None`` (Trinity's global layers do the same), 20 query heads on ONE
  KV head of 128: the pool row is one lane tile, stored flat.

Static branches are on a layer's own parameters: one with ``mamba`` is a
Mamba layer.  Decode steps and two-segment (tiled) batches only, as every
model with state slots.  ``num_experts > 1`` (the routed sibling) is
refused by name.

Layout (what ``checkpoint/hf_loader.py`` produces): every matrix [in, out];
``mamba/in_proj`` columns ``x | z``; ``mamba/conv1d/kernel`` [taps,
channels] with the last tap on the current token, ``conv1d/bias``
[channels]; ``mamba/x_proj`` columns ``dt_r | B | C``; ``mamba/A_log`` [N,
Di] (the published ``[Di, N]`` transposed: the state's layout).  Device
scopes under ``layers_<i>``: ``mamba/in_proj`` (norm and ``W_in``),
``mamba/conv``, ``mamba/x_proj`` (``W_x``, the three inner norms, ``W_dt``,
softplus), ``mamba/scan``, ``mamba/out`` (``+ D x``, ``* silu(z)``,
``W_out``); ``attn/*`` as RaggedLlama; ``mlp``.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from deepspeed_tpu.inference.v2.modules.attention import (
    _rms_norm,
    ragged_attention_block,
)
from deepspeed_tpu.inference.v2.modules.conv import _causal_conv, _silu
from deepspeed_tpu.inference.v2.ragged.kv_cache import CacheLayoutError
from deepspeed_tpu.ops.quantized_matmul import qmm
from deepspeed_tpu.ops.selective_scan import ssm_chunk, ssm_step

F32 = jnp.float32


@dataclasses.dataclass
class JambaConfig:
    vocab_size: int = 65536
    hidden_size: int = 2560
    intermediate_size: int = 8192
    num_hidden_layers: int = 28
    num_attention_heads: int = 20
    num_key_value_heads: int = 1
    #: layer ``l`` is attention when ``l % period == offset``
    attn_layer_period: int = 14
    attn_layer_offset: int = 7
    mamba_expand: int = 2
    mamba_d_state: int = 16
    mamba_d_conv: int = 4
    mamba_dt_rank: int = 160
    mamba_conv_bias: bool = True
    mamba_proj_bias: bool = False
    #: 1: a dense SwiGLU in every layer, no router
    num_experts: int = 1
    num_experts_per_tok: int = 1
    rms_norm_eps: float = 1e-6
    max_position_embeddings: int = 262144
    tie_word_embeddings: bool = True
    dtype: Any = jnp.bfloat16
    # read by the shared attention block
    sliding_window: Optional[int] = None

    def __post_init__(self):
        if self.num_experts > 1:
            raise NotImplementedError(
                f"num_experts={self.num_experts}: routed experts beside "
                f"state-space layers are not implemented (the dense "
                f"sibling, num_experts 1, is)")
        if self.mamba_proj_bias or self.sliding_window is not None:
            raise NotImplementedError(
                f"mamba_proj_bias={self.mamba_proj_bias}, sliding_window="
                f"{self.sliding_window}: the Mamba projections carry no "
                f"bias and the attention layers no window")
        if self.mamba_dt_rank == "auto":
            self.mamba_dt_rank = -(-self.hidden_size // 16)

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def d_inner(self) -> int:
        return self.mamba_expand * self.hidden_size

    def is_attention(self, i: int) -> bool:
        return i % self.attn_layer_period == self.attn_layer_offset


def param_shapes(cfg: JambaConfig) -> Dict[str, Any]:
    """The parameter tree :class:`RaggedJamba` reads, as shapes."""
    dt, h, f = cfg.dtype, cfg.hidden_size, cfg.intermediate_size
    hq, hkv, d = cfg.num_attention_heads, cfg.num_key_value_heads, \
        cfg.head_dim
    di, n, r = cfg.d_inner, cfg.mamba_d_state, cfg.mamba_dt_rank
    sds = lambda *shape: jax.ShapeDtypeStruct(shape, dt)
    kern = lambda i, o: {"kernel": sds(i, o)}

    def layer(i):
        mixer = {"self_attn": {
            "q_proj": kern(h, hq * d), "k_proj": kern(h, hkv * d),
            "v_proj": kern(h, hkv * d), "o_proj": kern(hq * d, h)}} \
            if cfg.is_attention(i) else {"mamba": {
                "in_proj": kern(h, 2 * di),
                "conv1d": {"kernel": sds(cfg.mamba_d_conv, di),
                           **({"bias": sds(di)} if cfg.mamba_conv_bias
                              else {})},
                "x_proj": kern(di, r + 2 * n),
                "dt_proj": {"kernel": sds(r, di), "bias": sds(di)},
                "A_log": sds(n, di), "D": sds(di),
                "dt_layernorm": {"scale": sds(r)},
                "b_layernorm": {"scale": sds(n)},
                "c_layernorm": {"scale": sds(n)},
                "out_proj": kern(di, h)}}
        return {"input_layernorm": {"scale": sds(h)},
                "pre_ff_layernorm": {"scale": sds(h)}, **mixer,
                "mlp": {"gate_proj": kern(h, f), "up_proj": kern(h, f),
                        "down_proj": kern(f, h)}}

    tree = {"embed_tokens": {"embedding": sds(cfg.vocab_size, h)},
            **{f"layers_{i}": layer(i)
               for i in range(cfg.num_hidden_layers)},
            "final_layernorm": {"scale": sds(h)}}
    if not cfg.tie_word_embeddings:
        tree["lm_head"] = kern(h, cfg.vocab_size)
    return tree


class RaggedJamba:
    """Callable ragged forward bound to a :class:`JambaConfig`."""

    #: the attention reads pass no scales: int8 pools are refused by the
    #: engine
    supports_quantized_kv = False

    def __init__(self, config: JambaConfig, block_size: int):
        self.config = config
        self.block_size = block_size
        self.tp = 1
        #: None: the scan's Mosaic kernels on a TPU, their XLA compositions
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
        """The per-sequence state the engine's slot pool holds: each Mamba
        layer's scan state (float32, the channels on the lanes) and the
        tail of its convolution (its ``K - 1`` rows of ``Di`` back to
        back in ONE row a slot, the layout of ``modules/conv.py``)."""
        cfg = self.config
        return {
            "layers": [i for i in range(cfg.num_hidden_layers)
                       if not cfg.is_attention(i)],
            "leaves": {
                "ssm": ((cfg.mamba_d_state, cfg.d_inner), F32),
                "conv": (((cfg.mamba_d_conv - 1) * cfg.d_inner,),
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
                "RaggedJamba runs decode steps and two-segment (tiled) "
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
                if "mamba" in lp:
                    out, new_cache[f"layer_{i}"] = self._mamba(
                        lp, x, cache[f"layer_{i}"], batch, prefill_tile)
                else:
                    with jax.named_scope("attn/qkv"):
                        xa = _rms_norm(x, lp["input_layernorm"]["scale"],
                                       cfg.rms_norm_eps)
                    # no positional embedding: cos = sin = None
                    out, new_cache[f"layer_{i}"] = ragged_attention_block(
                        lp["self_attn"], xa, cache[f"layer_{i}"], batch,
                        self.block_size, cfg, h, hkv, d, None, None,
                        prefill_tile=prefill_tile, decode_mode=decode)
                x = x + out
                with jax.named_scope("mlp"):
                    mlp = lp["mlp"]
                    xm = _rms_norm(x, lp["pre_ff_layernorm"]["scale"],
                                   cfg.rms_norm_eps)
                    x = x + qmm(
                        jax.nn.silu(qmm(xm, mlp["gate_proj"]["kernel"], dt))
                        * qmm(xm, mlp["up_proj"]["kernel"], dt),
                        mlp["down_proj"]["kernel"], dt)
        with jax.named_scope("lm_head"):
            x = _rms_norm(x, params["final_layernorm"]["scale"],
                          cfg.rms_norm_eps)
            x = x[batch["logits_idx"]]
            if cfg.tie_word_embeddings:
                logits = x @ embedding.T
            else:
                logits = x @ params["lm_head"]["kernel"].astype(dt)
        return logits, new_cache

    def _mamba(self, lp, x, layer_cache, batch, prefill_tile):
        """One Mamba mixer over the flat token buffer.  Returns ``(out [T,
        hidden], {"ssm", "conv"})``."""
        cfg, mb, dt = self.config, lp["mamba"], self.config.dtype
        n, r = cfg.mamba_d_state, cfg.mamba_dt_rank
        eps = cfg.rms_norm_eps
        pool = layer_cache["ssm"]
        scratch = pool.shape[0] - 1
        pos, sslot = batch["token_pos"], batch["state_slot"]
        t_rows, s_rows = x.shape[0], sslot.shape[0]
        with jax.named_scope("mamba/in_proj"):
            xn = _rms_norm(x, lp["input_layernorm"]["scale"], eps)
            u, z = jnp.split(qmm(xn, mb["in_proj"]["kernel"], dt), 2,
                             axis=-1)
        with jax.named_scope("mamba/conv"):
            u, conv = _causal_conv(u, mb["conv1d"]["kernel"],
                                   layer_cache["conv"], batch,
                                   bias=mb["conv1d"].get("bias"),
                                   prefill_tile=prefill_tile)
        with jax.named_scope("mamba/x_proj"):
            dbc = qmm(u, mb["x_proj"]["kernel"], dt)
            dt_r = _rms_norm(dbc[:, :r], mb["dt_layernorm"]["scale"], eps)
            b = _rms_norm(dbc[:, r:r + n], mb["b_layernorm"]["scale"],
                          eps).astype(F32)
            c = _rms_norm(dbc[:, r + n:], mb["c_layernorm"]["scale"],
                          eps).astype(F32)
            step = jax.nn.softplus(
                qmm(dt_r, mb["dt_proj"]["kernel"], dt).astype(F32)
                + mb["dt_proj"]["bias"].astype(F32))
            # pad rows: decay 1, input 0
            step = jnp.where((pos >= 0)[:, None], step, 0.0)
            u32 = u.astype(F32)
            dtx = step * u32
        with jax.named_scope("mamba/scan"):
            a = -jnp.exp(mb["A_log"].astype(F32))           # [N, Di]
            rows = slice(0, s_rows)             # one token a row
            row_slot = jnp.where(pos[rows] >= 0,
                                 sslot[batch["token_slot"][rows]], scratch)
            y, pool = ssm_step(pool, step[rows], dtx[rows], b[rows],
                               c[rows], a, row_slot, pos[rows] == 0,
                               interpret=self.interpret)
            if t_rows > s_rows:                 # the tile segment
                rows = slice(s_rows, t_rows)
                first = slice(s_rows, t_rows, int(prefill_tile))
                tile_slot = jnp.where(pos[first] >= 0,
                                      sslot[batch["token_slot"][first]],
                                      scratch)
                y2, pool = ssm_chunk(pool, step[rows], dtx[rows], b[rows],
                                     c[rows], a, tile_slot, pos[first] == 0,
                                     int(prefill_tile),
                                     interpret=self.interpret)
                y = jnp.concatenate([y, y2])
        with jax.named_scope("mamba/out"):
            y = (y + mb["D"].astype(F32) * u32) * _silu(z.astype(F32))
            out = qmm(y.astype(dt), mb["out_proj"]["kernel"], dt)
        return out, {"ssm": pool, "conv": conv}
