"""Ragged Ouro forward for the FastGen engine (``model_type: ouro``;
ByteDance Ouro is the family served): a looped language model.  ONE stack of
``num_hidden_layers`` Llama-shaped layers runs ``total_ut_steps`` times a
token, every pass with the same weights and a K/V cache of its own.

    h = E[ids]
    for pass t, for layer l:
        a = n(h; w_in[l]);  q, k, v = a Wq[l], a Wk[l], a Wv[l];  q, k = RoPE
        cache[l, t] <- (k, v);  o = softmax(q K^T / sqrt(d), causal) V
        h = h + n(o Wo[l]; w_in2[l])
        m = n(h; w_post[l])
        h = h + n((silu(m Wg[l]) * (m Wu[l])) Wd[l]; w_post2[l])
    after the last layer of EVERY pass: h = n(h; w_final), which the next
    pass starts from;  logits = h W_head after the last pass

What is new beside :class:`RaggedLlama`:

* **Cache layers are not weight layers** (``kv_passes`` below is how the
  engine learns of it).  The state manager builds each layer's pools
  ``kv_passes`` times as long, pass ``t`` in the rows from ``t x num_blocks x
  block_size`` (``ragged/kv_cache.py``), behind ONE allocator and ONE block
  table a sequence: a block id names the same offset in every pass.  Pass
  ``t`` here reads through ``block_tables + t x num_blocks`` and writes at
  ``kv_dest + t x num_blocks x block_size``; the shared attention block and
  both paged kernels see a pool and a table as they always did.
* **The passes are a loop IN the step program** (``lax.fori_loop`` over a
  body of ``num_hidden_layers`` layers, the hidden state and the pools its
  carry): a step program's text is one stack's, not ``kv_passes`` stacks',
  and so is the time to build it.  Written out ``kv_passes`` times in the
  text the same arithmetic took the same device time and three times as
  long to build (v5e, ``PERF.md`` section 6, PR 43; a test still compares
  the two, with ``fori_loop`` here replaced by a Python loop).
* **Four norms a block**: a norm before and after each branch (the shared
  ``_rms_norm``), and the final norm between the passes.
* **The exit gate** ``lambda_t = sigmoid(h_t w + b)`` on each pass's normed
  hidden state gives the exit distribution ``p_t = lambda_t prod_{j<t} (1 -
  lambda_j)``, the last pass taking what is left.  The published
  ``early_exit_threshold`` of 1 is never reached before the last pass, so
  every token runs every pass and the gate reaches no logit: it is
  :meth:`RaggedOuro.exit_distribution`, outside the step programs
  (``pass_hiddens=True`` hands back the states it is a function of).  A
  threshold below 1 is refused by name: a token that leaves early leaves no
  keys in the later passes' caches, which is another cache contract
  (``ROADMAP.md`` B).

Layout (what ``checkpoint/hf_loader.py`` produces): every matrix [in, out];
the four norms under their published names (``input_layernorm``,
``input_layernorm_2``, ``post_attention_layernorm``,
``post_attention_layernorm_2``); ``early_exit_gate`` ``{kernel [H, 1], bias
[1]}``.  Device scopes: ``embed``; under ``loop/layers_<i>`` the names every
Llama-shaped family has (``attn/qkv`` with the input norm, ``attn/rope_insert``,
``attn/dense_read`` or a paged kernel, ``attn/out_proj`` with its norm,
``mlp`` with both of its norms); ``pass_norm`` (the final norm, once a
pass); ``lm_head`` (the row gather and the head).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax.lax import fori_loop

from deepspeed_tpu.inference.v2.modules.attention import (
    _rms_norm,
    _rotary,
    ragged_attention_block,
)
from deepspeed_tpu.ops.quantized_matmul import qmm

F32 = jnp.float32


@dataclasses.dataclass
class OuroConfig:
    vocab_size: int = 49152
    hidden_size: int = 2048
    intermediate_size: int = 5632
    num_hidden_layers: int = 48
    num_attention_heads: int = 16
    num_key_value_heads: int = 16
    head_dim: int = 128
    #: passes of the stack a token
    total_ut_steps: int = 4
    early_exit_threshold: float = 1.0
    rope_theta: float = 1000000.0
    rope_scaling: Any = None
    rms_norm_eps: float = 1e-6
    max_position_embeddings: int = 65536
    sliding_window: Optional[int] = None
    use_sliding_window: bool = False
    tie_word_embeddings: bool = False
    dtype: Any = jnp.bfloat16

    def __post_init__(self):
        if self.early_exit_threshold < 1:
            raise NotImplementedError(
                f"early_exit_threshold={self.early_exit_threshold}: a token "
                f"that leaves the loop before the last pass leaves no keys "
                f"in the later passes' caches, and the rows of one step "
                f"would stand at different depths; every token runs all "
                f"{self.total_ut_steps} passes here (the published "
                f"threshold is 1)")
        if self.total_ut_steps < 1:
            raise ValueError(f"total_ut_steps={self.total_ut_steps}")
        if self.rope_scaling is not None:
            raise NotImplementedError(
                f"rope_scaling={self.rope_scaling!r}: a scaled rotary "
                f"embedding is not implemented (the published "
                f"configuration has none)")
        if self.use_sliding_window or self.sliding_window is not None:
            raise NotImplementedError(
                "a sliding window is not implemented for the looped stack "
                "(the published configuration has none)")
        if self.tie_word_embeddings:
            raise NotImplementedError(
                "tie_word_embeddings: the published model is untied")


def param_shapes(cfg: OuroConfig) -> Dict[str, Any]:
    """The parameter tree :class:`RaggedOuro` reads, as shapes."""
    dt, h, f = cfg.dtype, cfg.hidden_size, cfg.intermediate_size
    hq, hkv, d = cfg.num_attention_heads, cfg.num_key_value_heads, \
        cfg.head_dim
    sds = lambda *shape: jax.ShapeDtypeStruct(shape, dt)
    kern = lambda i, o: {"kernel": sds(i, o)}
    layer = {
        "input_layernorm": {"scale": sds(h)},
        "input_layernorm_2": {"scale": sds(h)},
        "post_attention_layernorm": {"scale": sds(h)},
        "post_attention_layernorm_2": {"scale": sds(h)},
        "self_attn": {"q_proj": kern(h, hq * d), "k_proj": kern(h, hkv * d),
                      "v_proj": kern(h, hkv * d), "o_proj": kern(hq * d, h)},
        "mlp": {"gate_proj": kern(h, f), "up_proj": kern(h, f),
                "down_proj": kern(f, h)}}
    return {"embed_tokens": {"embedding": sds(cfg.vocab_size, h)},
            **{f"layers_{i}": layer for i in range(cfg.num_hidden_layers)},
            "norm": {"scale": sds(h)},
            "early_exit_gate": {"kernel": sds(h, 1), "bias": sds(1)},
            "lm_head": kern(h, cfg.vocab_size)}


class RaggedOuro:
    """Callable ragged forward bound to an :class:`OuroConfig`."""

    #: the shared attention block quantizes on insert and threads scales
    supports_quantized_kv = True

    #: dtype of the hidden state between the layers (the loop's carry and
    #: each residual sum); the branches compute in ``config.dtype``
    stream = F32

    def __init__(self, config: OuroConfig, block_size: int):
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
    def kv_passes(self) -> int:
        """K/V caches each layer keeps: one a pass of the stack."""
        return self.config.total_ut_steps

    def __call__(self, params: Dict[str, Any], cache: Dict[str, Any],
                 batch: Dict[str, jax.Array], prefill_tile=None,
                 decode=False, verify_k=None, pass_hiddens=False):
        """Returns ``(logits [S, vocab], new cache)``; with ``pass_hiddens``
        (static; no step program sets it) also ``[passes, S, hidden]``, each
        pass's normed hidden state at the logits rows."""
        cfg = self.config
        dt, passes = cfg.dtype, cfg.total_ut_steps
        with jax.named_scope("embed"):
            x = params["embed_tokens"]["embedding"].astype(dt)[
                batch["token_ids"]].astype(self.stream)
        cos, sin = _rotary(batch["token_pos"], cfg.head_dim, cfg.rope_theta)
        # rows of one pass in a pool: where pass t's cache starts
        pass_rows = next(iter(cache.values()))["k"].shape[0] // passes
        seen = jnp.zeros((passes, batch["logits_idx"].shape[0],
                          cfg.hidden_size), x.dtype) if pass_hiddens else None

        def one_pass(t, carry):
            x, cache, seen = carry
            x, cache = self._one_pass(
                params, t, x, cache, self._pass_view(batch, t, pass_rows),
                cos, sin, prefill_tile, decode, verify_k)
            if seen is not None:
                seen = jax.lax.dynamic_update_index_in_dim(
                    seen, x[batch["logits_idx"]], t, 0)
            return x, cache, seen

        x, new_cache, seen = fori_loop(0, passes, one_pass,
                                       (x, cache, seen))
        with jax.named_scope("lm_head"):
            logits = qmm(x[batch["logits_idx"]].astype(dt),
                         params["lm_head"]["kernel"], dt)
        if pass_hiddens:
            return logits, new_cache, seen
        return logits, new_cache

    def _pass_view(self, batch, t, pass_rows: int):
        """Pass ``t``'s view of the batch: the same tables and write
        targets, moved to its part of every pool."""
        return {**batch,
                "block_tables": batch["block_tables"]
                + t * (pass_rows // self.block_size),
                "kv_dest": batch["kv_dest"] + t * pass_rows}

    def _one_pass(self, params, t, x, cache, batch, cos, sin, prefill_tile,
                  decode, verify_k):
        """Pass ``t``: the layers over its caches (``batch`` is its view),
        then the final norm; ``(x, new cache)``."""
        cfg = self.config
        h, hkv, d = (cfg.num_attention_heads, cfg.num_key_value_heads,
                     cfg.head_dim)
        new_cache = {}
        with jax.named_scope("loop"):
            for i in range(cfg.num_hidden_layers):
                lp = params[f"layers_{i}"]
                with jax.named_scope(f"layers_{i}"):
                    with jax.named_scope("attn/qkv"):
                        xa = _rms_norm(x, lp["input_layernorm"]["scale"],
                                       cfg.rms_norm_eps).astype(cfg.dtype)
                    out, new_cache[f"layer_{i}"] = ragged_attention_block(
                        lp["self_attn"], xa, cache[f"layer_{i}"], batch,
                        self.block_size, cfg, h, hkv, d, cos, sin,
                        prefill_tile=prefill_tile, decode_mode=decode,
                        verify_k=verify_k)
                    with jax.named_scope("attn/out_proj"):
                        x = x + _rms_norm(out.astype(x.dtype),
                                          lp["input_layernorm_2"]["scale"],
                                          cfg.rms_norm_eps)
                    with jax.named_scope("mlp"):
                        x = x + self._mlp(lp, x)
        with jax.named_scope("pass_norm"):
            x = _rms_norm(x, params["norm"]["scale"], cfg.rms_norm_eps)
        return x, new_cache

    def _mlp(self, lp, x):
        """The SwiGLU branch between its two norms."""
        cfg, mlp, dt = self.config, lp["mlp"], self.config.dtype
        xm = _rms_norm(x, lp["post_attention_layernorm"]["scale"],
                       cfg.rms_norm_eps).astype(dt)
        y = qmm(jax.nn.silu(qmm(xm, mlp["gate_proj"]["kernel"], dt))
                * qmm(xm, mlp["up_proj"]["kernel"], dt),
                mlp["down_proj"]["kernel"], dt)
        return _rms_norm(y.astype(x.dtype),
                         lp["post_attention_layernorm_2"]["scale"],
                         cfg.rms_norm_eps)

    def exit_distribution(self, params, hiddens):
        """The exit gate's distribution over the passes, from each pass's
        normed hidden state ``[passes, rows, hidden]`` (``pass_hiddens``):
        ``(lambda [passes, rows], p [passes, rows])`` in float32, ``p_t =
        lambda_t prod_{j<t} (1 - lambda_j)`` and the last pass what is
        left, so ``p`` sums to 1 over the passes."""
        gate = params["early_exit_gate"]
        lam = jax.nn.sigmoid(
            hiddens.astype(F32) @ gate["kernel"].astype(F32)[:, 0]
            + gate["bias"].astype(F32)[0])
        stay = jnp.cumprod(1.0 - lam, axis=0)
        before = jnp.concatenate([jnp.ones_like(stay[:1]), stay[:-1]])
        return lam, jnp.concatenate([(lam * before)[:-1], before[-1:]])
