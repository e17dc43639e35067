"""Ragged (paged-KV) Llama forward for the FastGen engine.

Reference analog: ``inference/v2/model_implementations/llama_v2`` built on
``DSTransformerModelBase`` (inference_transformer_base.py:47), whose layer
loop calls the CUDA ragged kernels (★linear_blocked_kv_rotary → ★blocked_flash
→ cutlass GEMMs, SURVEY §3.5).

TPU-native design: ONE jitted program consumes the packed token buffer that
:class:`RaggedBatchWrapper.finalize` builds (static shapes: token budget T,
max sequences S, block-table width B) and the flat paged KV pool from
:class:`BlockedKVCache`:

* token embeddings / projections / MLP run over the flat ``[T, H]`` buffer —
  ragged batching is free on the MXU because tokens from different sequences
  are just rows of the same matmul;
* KV writes are one ``scatter`` to ``kv_dest`` (pad lanes write to the trash
  block) and attention reads each slot's context through its block table:
  the shared block and the route to a kernel are ``modules/attention.py``.

Tensor parallelism (reference ``inference/v2/model_implementations/sharding/
{qkv,attn,attn_out,mlp,embedding,unembed}.py``): a ``shard_map`` over the
'model' mesh axis with Megatron-style splits —

* embedding vocab-split (masked local lookup + psum),
* QKV / gate / up column-split (each shard owns ``H/tp`` heads and the
  matching slice of the KV pool; the paged kernel runs on the LOCAL shard),
* attn-out / down row-split followed by the ONLY two per-layer all-reduces,
* unembed (lm_head) vocab-split with an all-gather of the per-slot logits.

The param tree is EXACTLY :class:`models.llama.LlamaForCausalLM`'s, so v1 and
v2 engines share checkpoints and the continuous-batching correctness test can
compare the two token-for-token.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from deepspeed_tpu.inference.v2.modules.attention import (
    _rms_norm,
    _rotary,
    kv_spec,
    ragged_attention_block,
    ragged_param_specs,
)
from deepspeed_tpu.models.llama import LlamaConfig
from deepspeed_tpu.ops.quantized_matmul import qmm


class RaggedLlama:
    """Callable ragged forward bound to a :class:`LlamaConfig`.

    ``mesh`` with a non-trivial 'model' axis turns on tensor parallelism:
    ``__call__`` becomes a shard_map over that axis (params/KV pool must be
    placed with ``modules/attention.py``'s :func:`shard_ragged_params` /
    :func:`kv_spec` — the engine does this).
    """

    #: the shared ragged_attention_block write path quantizes on insert
    #: and threads scales — int8 KV (kv_cache.dtype="int8") is supported
    supports_quantized_kv = True

    def __init__(self, config: LlamaConfig, block_size: int,
                 mesh: Optional[Mesh] = None, tp_axis: str = "model"):
        self.config = config
        self.block_size = block_size
        self.tp_axis = tp_axis
        self.mesh = None
        self.tp = 1
        if mesh is not None and mesh.shape.get(tp_axis, 1) > 1:
            self.bind_mesh(mesh, tp_axis)

    def bind_mesh(self, mesh: Mesh, tp_axis: str = "model") -> None:
        tp = mesh.shape[tp_axis]
        cfg = self.config
        for name, n in (("num_attention_heads", cfg.num_attention_heads),
                        ("num_key_value_heads", cfg.num_key_value_heads),
                        ("vocab_size", cfg.vocab_size),
                        ("intermediate_size", cfg.intermediate_size)):
            if n % tp != 0:
                raise ValueError(
                    f"FastGen TP: {name}={n} not divisible by "
                    f"model-parallel degree {tp}")
        self.mesh = mesh
        self.tp_axis = tp_axis
        self.tp = tp

    @property
    def num_layers(self):
        return self.config.num_hidden_layers

    @property
    def num_kv_heads(self):
        return self.config.num_key_value_heads

    @property
    def head_dim(self):
        return self.config.head_dim

    def __call__(self, params: Dict[str, Any], kv_cache: Dict[str, Any],
                 batch: Dict[str, jax.Array], prefill_tile=None,
                 decode=False, verify_k=None):
        """Run one ragged forward.

        Returns ``(logits [S, vocab], new_kv_cache)`` where row ``s`` holds
        the logits of slot ``s``'s LAST scheduled token. ``prefill_tile``
        (static) marks a two-segment batch (single-token rows, then whole
        tiles) -> dense/decode read + tiled prefill kernel;
        ``decode`` (static) marks a one-token-per-slot batch with
        ``token_slot == arange`` -> decode-optimised attention path;
        ``verify_k`` (static) marks a speculative verify batch — K
        consecutive-position tokens per slot, rows slot-major — routed
        to the fused multi-query verify kernel on TPU (the batch's
        ``logits_idx`` selects EVERY row, so the caller gets all K
        candidate logits per slot).
        """
        if self.tp == 1:
            return self._forward(params, kv_cache, batch, ax=None,
                                 prefill_tile=prefill_tile, decode=decode,
                                 verify_k=verify_k)
        param_specs = ragged_param_specs(params)
        cache_specs = jax.tree.map(kv_spec, kv_cache)
        batch_specs = jax.tree.map(lambda _x: P(), batch)
        fwd = functools.partial(self._forward, ax=self.tp_axis,
                                prefill_tile=prefill_tile, decode=decode,
                                verify_k=verify_k)
        return jax.shard_map(
            fwd, mesh=self.mesh,
            in_specs=(param_specs, cache_specs, batch_specs),
            out_specs=(P(), cache_specs),
            check_vma=False,
        )(params, kv_cache, batch)

    # ------------------------------------------------------------------ #
    def _embed(self, emb, token_ids, ax):
        """Vocab-parallel embedding (reference sharding/embedding.py):
        masked local-range lookup + psum."""
        if ax is None:
            return emb[token_ids]
        v_local = emb.shape[0]
        start = jax.lax.axis_index(ax) * v_local
        loc = token_ids - start
        ok = (loc >= 0) & (loc < v_local)
        x = jnp.where(ok[:, None], emb[jnp.clip(loc, 0, v_local - 1)], 0)
        return jax.lax.psum(x, ax)

    def _forward(self, params, kv_cache, batch, *, ax, prefill_tile=None,
                 decode=False, verify_k=None):
        cfg = self.config
        m = params["model"]
        dt = cfg.dtype
        tp = self.tp if ax is not None else 1
        token_ids = batch["token_ids"]            # [T]
        token_pos = batch["token_pos"]            # [T]

        with jax.named_scope("embed"):
            x = self._embed(m["embed_tokens"]["embedding"].astype(dt),
                            token_ids, ax)                         # [T, H]
        h, hkv, d = (cfg.num_attention_heads // tp,
                     cfg.num_key_value_heads // tp, cfg.head_dim)
        cos, sin = _rotary(token_pos, d, cfg.rope_theta)
        new_cache = {}
        # device scopes (op_name of every operation): layers_<i>/attn/qkv
        # (with its norm), attn/rope_insert, attn/dense_read (the read of
        # one-token rows: the decode walk, or the dense XLA read) or
        # attn/gather_read or another paged_* kernel, attn/out_proj, mlp
        # (with its norm), then lm_head
        for i in range(cfg.num_hidden_layers):
            lp = m[f"layers_{i}"]
            with jax.named_scope(f"layers_{i}"):
                with jax.named_scope("attn/qkv"):
                    xa = _rms_norm(x, lp["input_layernorm"]["scale"],
                                   cfg.rms_norm_eps)
                out, new_cache[f"layer_{i}"] = ragged_attention_block(
                    lp["self_attn"], xa, kv_cache[f"layer_{i}"], batch,
                    self.block_size, cfg, h, hkv, d, cos, sin, ax=ax,
                    prefill_tile=prefill_tile, decode_mode=decode,
                    verify_k=verify_k)
                x = x + out
                with jax.named_scope("mlp"):
                    x = x + self._mlp(lp, x, ax)
        with jax.named_scope("lm_head"):
            x = _rms_norm(x, m["norm"]["scale"], cfg.rms_norm_eps)
            # ★logits_gather analog: slice each slot's last token BEFORE
            # the unembed matmul — [S, H] @ [H, V] instead of [T, V] over
            # every packed token row (a SplitFuse prefill bucket is T >> S,
            # so the full-width unembed wastes T/S of the vocab matmul and
            # its [T, V] HBM writes); (TP) all-gathers only the [S, V/tp]
            # slice (reference sharding/unembed.py gathers the sliced
            # logits too)
            x = x[batch["logits_idx"]]
            if cfg.tie_word_embeddings:
                logits = x @ m["embed_tokens"]["embedding"].astype(dt).T
            else:
                logits = qmm(x, params["lm_head"]["kernel"], dt)
            if ax is not None:
                logits = jax.lax.all_gather(logits, ax, axis=1, tiled=True)
        return logits, new_cache

    def _mlp(self, lp, x, ax):
        """Post-attention norm and the SwiGLU MLP of one layer."""
        cfg, mlp, dt = self.config, lp["mlp"], self.config.dtype
        xm = _rms_norm(x, lp["post_attention_layernorm"]["scale"],
                       cfg.rms_norm_eps)
        gate = qmm(xm, mlp["gate_proj"]["kernel"], dt)
        up = qmm(xm, mlp["up_proj"]["kernel"], dt)
        mo = qmm(jax.nn.silu(gate) * up, mlp["down_proj"]["kernel"], dt)
        if ax is not None:
            mo = jax.lax.psum(mo, ax)             # row-parallel mlp-down
        return mo
