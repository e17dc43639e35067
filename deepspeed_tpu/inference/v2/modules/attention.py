"""Attention over the paged KV pool, for every ragged model family
(reference: ``inference/v2/modules/``).  :func:`_paged_attention` is the ONE
place that chooses a paged-attention read (a Pallas kernel of
``kernels/blocked_flash.py`` or an XLA composition, which is also the
kernels' test reference); ``on_tpu`` here is what a test patches to take the
chip's route in interpret mode.  :func:`ragged_attention_block` is the layer
body of the families that keep per-head keys and values, :func:`_rope_insert`
/ :func:`insert_kv` its write half; the norms and :func:`_rotary` serve
every family; :func:`ragged_param_specs` / :func:`shard_ragged_params` /
:func:`kv_spec` split what these layers read over a 'model' mesh axis.
(``model_implementations/`` -> here -> ``kernels/``, ``ops/``, ``ragged/``.)"""

from __future__ import annotations

import math
import re
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from deepspeed_tpu.analysis.registry import pallas_kernel_case
from deepspeed_tpu.models.llama import apply_rotary

# Megatron split rules over the 'model' axis (reference
# inference/v2/model_implementations/sharding/*.py) — serving shares the
# training rules so a sharding change propagates to both
from deepspeed_tpu.models.llama import LLAMA_PARTITION_RULES as _TP_RULES
from deepspeed_tpu.ops.quantized_matmul import qmm
from deepspeed_tpu.utils.platform import on_tpu


def ragged_param_specs(params) -> Any:
    """PartitionSpec tree for the ragged Llama param tree."""
    def spec_for(path, _leaf):
        name = "/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                        for k in path)
        for pat, spec in _TP_RULES:
            if re.search(pat, name):
                return spec
        return P()

    return jax.tree_util.tree_map_with_path(spec_for, params)


def shard_ragged_params(params, mesh: Mesh) -> Any:
    """Place a (host or replicated) param tree sharded for TP serving."""
    specs = ragged_param_specs(params)
    return jax.tree.map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)), params, specs)


def kv_spec(leaf) -> P:
    """A pool leaf's spec under TP: the KV heads split, which is a lane
    range of the flat row [rows, Hkv*D] and the middle dimension of a
    [rows, Hkv, D] pool (and of an int8 pool's [rows, Hkv] scales)."""
    return P(None, "model", *(None,) * (leaf.ndim - 2))


def _layer_norm(x, p, eps):
    """Param-dict LayerNorm for ragged models (OPT/Falcon/GPT-style) —
    delegates to the single fp32-upcast implementation in
    ops/transformer.py."""
    from deepspeed_tpu.ops.transformer import layer_norm

    return layer_norm(x, p["scale"], p["bias"], eps)


def _rms_norm(x, scale, eps):
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    return (x32 * jax.lax.rsqrt(var + eps)
            * scale.astype(jnp.float32)).astype(x.dtype)


def _rms_norm_1p(x, scale, eps):
    """RMSNorm with a zero-centred weight: ``x / rms(x) * (1 + w)``."""
    return _rms_norm(x, 1.0 + scale.astype(jnp.float32), eps)


def _paged_attention(q, k_pool, v_pool, batch, block_size,
                     use_kernel=None, window=None, prefill_tile=None,
                     decode_mode=False, verify_k=None,
                     k_scale=None, v_scale=None):
    """Paged attention over the blocked KV pool.

    q: [T, H, D]; k_pool/v_pool: the pool as ``BlockedKVCache`` stores it,
    the flat row [num_blocks*bs, Hkv*D] (a float pool of whole-tile rows)
    or [num_blocks*bs, Hkv, D].  Returns [T, H, D]. Under TP the caller
    passes LOCAL heads — the kernel is oblivious to the mesh. ``window`` =
    Mistral sliding-window width.

    On TPU a ``put`` forward routes to the Pallas blocked-flash kernels
    (inference/v2/kernels/blocked_flash.py): block tables drive the
    kernel's DMA schedule, so no [T, C, Hkv, D] context gather is ever
    materialised.  ``prefill_tile`` (static; set by an engine that packs
    the two-segment layout, ``RaggedBatchWrapper.set_alignment``) says the
    first S rows (S = the block table's height) are single-token rows and
    the rest whole tiles: the tiles go to the TILED kernel — grid (tiles,
    key steps): a step is a run of table entries counted from the tile's
    first live block (the band alone on a window layer, whatever the
    table's width), a KV head's group of query heads stacked into one pair
    of MXU dots and one online-softmax update; the reference's
    atom_builder work-unit shape — and the single-token rows to the read a
    decode step takes
    (:func:`_single_row_read`).  Without it (a token budget that is
    no whole number of tiles) the whole buffer goes to the token-grid
    kernel, grid (tokens, blocks).

    ``decode_mode`` (static; engine decode programs set it) asserts
    T == S with ``token_slot == arange(S)``.  On TPU, on a pool the DMA
    walk can copy (``decode_walk_usable``: a float pool in the flat row
    ``[rows, Hkv*D]`` of whole lane tiles, at heads of whole tiles or heads
    that divide one; such a pool arrives here 2-D and goes to the walk and
    to the tiled kernel as it is stored; an int8 pool at ``D % 128 ==
    0``), it routes to the manual-DMA decode kernel
    (:func:`deepspeed_tpu.inference.v2.kernels.paged_decode_attention`):
    each row reads exactly the blocks its table holds up to its position,
    so what the read costs follows what the rows hold (their
    ``token_pos``), whatever the pool's size.  The device scope of that
    read is ``attn/dense_read``, the name the XLA dense read below had
    when it was the cells' route (PERF.md section 7: the benchmark's
    readers match it).

    Off the kernels (CPU; pools the walk cannot copy: a row that is no
    whole number of lane tiles, kept [rows, Hkv, D]) a decode step takes
    one of two XLA compositions, on the pool's per-head view, which are
    also the references the kernels are tested against: the masked dense
    read of the whole pool while the pool is no larger than twice what the
    tables could hold, the gather bounded by the table extent beyond.

    ``k_scale``/``v_scale`` (int8 pools; ``[rows, Hkv]`` fp32) select
    the block-quantized mode: the hot decode/verify Pallas kernels fuse
    the per-row/per-head dequant into their HBM block walk; every other
    path dequantizes at its gather/read site (XLA fuses the cast-and-
    scale into the consuming einsum).
    """
    quantized = k_scale is not None
    if use_kernel is None:
        use_kernel = on_tpu()
    S = batch["block_tables"].shape[0]
    # the flat pool row: the walks (decode, verify) and the tiled kernel
    # read it as stored; the XLA reads and the token-grid kernel (a budget of
    # no whole tiles; no cell runs it) its per-head view
    flat_k, flat_v = k_pool, v_pool
    k_pool, v_pool = _head_view(k_pool, q), _head_view(v_pool, q)
    if use_kernel:
        from deepspeed_tpu.inference.v2.kernels import (
            decode_walk_usable, paged_attention, paged_attention_usable,
            paged_decode_attention, paged_prefill_attention,
            paged_verify_attention)

        if paged_attention_usable(q, k_pool, block_size):
            w = int(window) if window is not None else None
            meta = (batch["block_tables"], batch["token_slot"],
                    batch["token_pos"])
            # the manual-DMA walks (decode, verify) copy the pool's flat
            # [bs, Hkv*D] blocks as they are stored
            walk = decode_walk_usable(q.shape[-1], flat_k)
            if verify_k and q.shape[-1] % 128 == 0:
                # speculative multi-token verify: K query rows per slot
                # share one block walk (the fused multi-query variant of
                # the decode kernel).  Smaller head dims fall through to
                # the generic grid kernel, which handles verify-shaped
                # metadata unchanged.
                return paged_verify_attention(
                    q, flat_k, flat_v, *meta, block_size=block_size,
                    k_tokens=int(verify_k), window=w, k_scale=k_scale,
                    v_scale=v_scale)
            if decode_mode:
                if walk:
                    with jax.named_scope("attn/dense_read"):
                        return paged_decode_attention(
                            q, flat_k, flat_v, *meta, block_size=block_size,
                            window=w, k_scale=k_scale, v_scale=v_scale)
            elif quantized:
                # prefill kernels are not scale-aware (prefill is
                # compute-bound — the int8 win is decode bandwidth);
                # quantized prefill takes the XLA gather+dequant below
                pass
            elif prefill_tile:
                tables, slot, pos = meta
                single = _single_row_read(
                    q[:S], flat_k, flat_v, tables, slot[:S], pos[:S],
                    block_size, w)
                if q.shape[0] == S:          # no chunk longer than a token
                    return single
                return jnp.concatenate([single, paged_prefill_attention(
                    q[S:], flat_k, flat_v, tables, slot[S:], pos[S:],
                    block_size=block_size, tile_q=int(prefill_tile),
                    window=w)])
            else:
                return paged_attention(
                    q, k_pool, v_pool, *meta, block_size=block_size,
                    window=w)
    if decode_mode and not _big_pool(k_pool, batch, block_size):
        with jax.named_scope("attn/dense_read"):
            return _dense_pool_read(q, k_pool, v_pool, k_scale, v_scale,
                                    batch, block_size, window)
    with jax.named_scope("attn/gather_read"):
        return _gather_read(q, k_pool, v_pool, k_scale, v_scale, batch,
                            block_size, window, decode_mode)


def _head_view(pool, q):
    """[rows, Hkv, D] of a pool: itself, or the per-head view of the flat
    row [rows, Hkv*D] (on the chip a copy of the pool: the reads the cells
    run never take it)."""
    return pool.reshape(pool.shape[0], -1, q.shape[-1]) if pool.ndim == 2 \
        else pool


def _big_pool(k_pool, batch, block_size) -> bool:
    """Off the decode walk only: is the pool larger than twice what the
    block tables could hold?  Then a read bounded by the table extent
    (the gather; the token-grid kernel) beats one of every pool row."""
    S, B = batch["block_tables"].shape
    return k_pool.shape[0] > 2 * S * B * block_size


def _single_row_read(q, k_pool, v_pool, tables, slot, pos, block_size,
                     window):
    """The single-token segment of a two-segment batch on TPU (float
    pool): the read a decode step takes — the manual-DMA walk over the
    blocks each row holds, in the device scope ``attn/dense_read`` —
    except that the rows' slots are in no order and pad rows (position
    -1) sit between them, which the walk takes.  On a pool the walk
    cannot copy (``decode_walk_usable``: a row that is no whole number of
    lane tiles) these few rows go through the token-grid kernel on a big
    pool and the dense XLA read on a tight one."""
    from deepspeed_tpu.inference.v2.kernels import (decode_walk_usable,
                                                    paged_attention,
                                                    paged_decode_attention)

    batch = {"block_tables": tables, "token_slot": slot, "token_pos": pos}
    walk = decode_walk_usable(q.shape[-1], k_pool)
    if not walk:                          # a flat row off the walk
        k_pool, v_pool = _head_view(k_pool, q), _head_view(v_pool, q)
    if not walk and _big_pool(k_pool, batch, block_size):
        return paged_attention(q, k_pool, v_pool, tables, slot, pos,
                               block_size=block_size, window=window)
    with jax.named_scope("attn/dense_read"):
        if walk:
            return paged_decode_attention(
                q, k_pool, v_pool, tables, slot, pos,
                block_size=block_size, window=window)
        return _dense_pool_read(q, k_pool, v_pool, None, None, batch,
                                block_size, window)


def _dense_pool_read(q, k_pool, v_pool, k_scale, v_scale, batch, block_size,
                     window):
    """The one-token-a-row read off the decode walk, on a tight pool
    (device scope ``attn/dense_read``): a decode step's rows, or the
    single-token rows of a two-segment batch, whose slots are in no order
    and whose pad rows carry position -1 (they attend nothing and come out
    finite).  The CPU's route, on the chip that of a row that is no whole
    number of lane tiles, and the reference the walk is tested against."""
    from deepspeed_tpu.inference.v2.ragged.kv_cache import dequantize_kv

    quantized = k_scale is not None
    block_tables = batch["block_tables"]          # [S, B]
    token_slot = batch["token_slot"]              # [T]
    token_pos = batch["token_pos"]                # [T]
    hkv = k_pool.shape[1]
    group = q.shape[1] // hkv
    # Masked DENSE attention over the whole pool: every pool row is read
    # ONCE, held or not — no [T, C, Hkv, D] gather copy, no Pallas grid
    # overhead.  At a head size the decode walk cannot copy it is the
    # cheaper of the two XLA reads while the pool is within twice the
    # table extent (0.46 vs 1.7 ms/step for 12 layers of a 125M-GQA d64
    # model on v5e); where the walk runs, the walk is faster at every
    # share of the pool held (PERF.md section 5, PR 30).  Visibility is
    # derived PER TOKEN against that token's own block table — NOT via a
    # row->owner scatter, which breaks under the prefix cache where one
    # warm block legitimately sits in several sequences' tables
    # (last-write-wins ownership would mask a shared block out of every
    # table but one).  The [T, B, rows] compare is decode-sized (T == S)
    # and XLA CSE dedupes it across layers.  Pools much larger than the
    # table extent (rows > 2*S*C) take the gather path below instead,
    # which is bounded by the block-table extent.
    from deepspeed_tpu.inference.v2.ragged.blocked_allocator import (
        BlockedAllocator)

    trash = BlockedAllocator.TRASH_BLOCK
    if quantized:
        # pool-wide dequant matches this branch's pool-wide read
        # (it only runs when rows <= 2*S*C, i.e. pool ~ live)
        k_pool = dequantize_kv(k_pool, k_scale, jnp.float32)
        v_pool = dequantize_kv(v_pool, v_scale, jnp.float32)
    rows = k_pool.shape[0]
    rowblk = jnp.arange(rows, dtype=jnp.int32) // block_size
    rowoff = jnp.arange(rows, dtype=jnp.int32) % block_size
    tbl = block_tables[token_slot]                         # [T, B]
    match = tbl[:, :, None] == rowblk[None, None, :]       # [T, B, rows]
    # absolute position of each visible row in ITS table slot
    j_idx = jnp.argmax(match, axis=1).astype(jnp.int32)    # [T, rows]
    row_pos = j_idx * block_size + rowoff[None, :]
    qg = q.reshape(q.shape[0], hkv, group, q.shape[2])
    scores = jnp.einsum("tkgd,rkd->tkgr", qg, k_pool,
                        preferred_element_type=jnp.float32) / jnp.sqrt(
        jnp.float32(q.shape[-1]))
    keep = (jnp.any(match, axis=1)
            & (row_pos <= token_pos[:, None])
            & (rowblk != trash)[None, :])                  # [T, rows]
    if window is not None:
        keep &= row_pos > token_pos[:, None] - window
    # FINITE mask value: a pad slot owns no rows, so -inf would
    # softmax to NaN and poison the residual stream
    scores = jnp.where(keep[:, None, None, :], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("tkgr,rkd->tkgd", probs.astype(v_pool.dtype),
                     v_pool, preferred_element_type=jnp.float32)
    return out.reshape(q.shape).astype(q.dtype)


def _gather_read(q, k_pool, v_pool, k_scale, v_scale, batch, block_size,
                 window, decode_mode):
    """The XLA gather composition (device scope ``attn/gather_read``): the
    reference/CPU path, large-pool decode off the kernels, quantized
    prefill.  Quantized pools dequantize at the READ site, never the whole
    pool up front: this branch serves the pool >> live capacity regime,
    where an O(pool) f32 materialization would cost 4x the memory int8
    just saved."""
    from deepspeed_tpu.inference.v2.ragged.kv_cache import dequantize_kv

    quantized = k_scale is not None
    block_tables = batch["block_tables"]          # [S, B]
    token_slot = batch["token_slot"]              # [T]
    token_pos = batch["token_pos"]                # [T]
    S, B = block_tables.shape
    C = B * block_size
    hkv = k_pool.shape[1]
    group = q.shape[1] // hkv

    # Gather each slot's context: [S, C, Hkv, D].  Context index == absolute
    # position because block tables are append-ordered.
    flat_idx = (block_tables[:, :, None] * block_size
                + jnp.arange(block_size, dtype=jnp.int32)[None, None, :]
                ).reshape(S, C)
    k_ctx = k_pool[flat_idx]                      # [S, C, Hkv, D]
    v_ctx = v_pool[flat_idx]
    if quantized:
        # dequantize the GATHERED slice — O(S*C) work and memory, never
        # the whole pool; gather-then-dequant is bitwise identical to
        # dequant-then-gather (dequant is per-row elementwise)
        k_ctx = dequantize_kv(k_ctx, k_scale[flat_idx], jnp.float32)
        v_ctx = dequantize_kv(v_ctx, v_scale[flat_idx], jnp.float32)

    if decode_mode:
        # large-pool decode: T == S with token_slot == arange, so the
        # per-token slot gather is the identity; keep the pool dtype
        # (bf16 MXU reads, fp32 accumulation)
        k_t, v_t = k_ctx, v_ctx
        qg = q.reshape(q.shape[0], hkv, group, q.shape[2])
        scores = jnp.einsum("tkgd,tckd->tkgc", qg, k_t,
                            preferred_element_type=jnp.float32) / jnp.sqrt(
            jnp.float32(q.shape[-1]))
        key_pos = jnp.arange(C, dtype=jnp.int32)[None, :]
        mask = key_pos <= token_pos[:, None]
        if window is not None:
            mask &= key_pos > token_pos[:, None] - window
        scores = jnp.where(mask[:, None, None, :], scores, -1e30)
        probs = jax.nn.softmax(scores, axis=-1)
        out = jnp.einsum("tkgc,tckd->tkgd", probs.astype(v_t.dtype), v_t,
                         preferred_element_type=jnp.float32)
        return out.reshape(q.shape).astype(q.dtype)

    # Per-token context via slot gather: [T, C, Hkv, D].
    k_t = k_ctx[token_slot].astype(jnp.float32)
    v_t = v_ctx[token_slot].astype(jnp.float32)

    # [T, H, D] x [T, C, Hkv, D] -> [T, H, C] (GQA: head h uses kv head h//g)
    qg = q.astype(jnp.float32).reshape(q.shape[0], hkv, group, q.shape[2])
    scores = jnp.einsum("tkgd,tckd->tkgc", qg, k_t) / jnp.sqrt(
        jnp.float32(q.shape[-1]))
    key_pos = jnp.arange(C, dtype=jnp.int32)[None, :]
    mask = key_pos <= token_pos[:, None]          # [T, C]
    if window is not None:
        mask &= key_pos > token_pos[:, None] - window
    # FINITE mask value: with -inf an all-masked row (tile-aligned pads
    # carry position -1) softmaxes to NaN, the NaN hidden state is written
    # to the trash block, and 0 * NaN poisons REAL rows via the masked
    # context lanes of the next layer's einsum
    scores = jnp.where(mask[:, None, None, :], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("tkgc,tckd->tkgd", probs, v_t)
    return out.reshape(q.shape).astype(q.dtype)


def ragged_attention_block(lp_attn, xa, layer_cache, batch, block_size, cfg,
                           h, hkv, d, cos, sin, ax=None,
                           prefill_tile=None, decode_mode=False,
                           verify_k=None):
    """Shared per-layer attention body (RaggedLlama + RaggedMixtral):
    qkv proj (→ q/k RMSNorm where the layer has ``q_norm``/``k_norm``)
    → rotary → paged-KV scatter → blocked-flash → o_proj
    (+ row-parallel psum under TP). ``h``/``hkv`` are LOCAL head counts.
    Returns ``(attn_out [T, H_model], new_layer_cache)``.

    Static branches, each read from the layer's own parameters or the
    config: a ``q_norm`` scale as long as ONE head normalises every head on
    its own (after the head split), a longer one the whole projection;
    ``cfg.zero_centered_norm`` makes those norms ``1 + w``; ``cos``/``sin``
    narrower than half a head rotate only the head's first dims (partial
    rotary); ``cfg.attn_output_gate``: ``q_proj`` emits, per head, the
    query and a gate, and the attention output is multiplied by the gate's
    sigmoid before ``o_proj``; ``cfg.query_scale`` (Granite's
    ``attention_multiplier x sqrt(d)``): ``q`` times it right after the
    projection, so the ``1/sqrt(d)`` of every read below is that model's
    own scale.  A config without the attribute keeps its program to the
    letter."""
    dt = cfg.dtype
    kv_dest = batch["kv_dest"]
    # OLMoE / OLMo-2 (static: the layer's own parameters say so): RMSNorm
    # over the WHOLE q and k projections, all heads at once, before the
    # head split and the rotary embedding
    qk_norm = "q_norm" in lp_attn
    headwise = qk_norm and lp_attn["q_norm"]["scale"].shape[-1] == d != h * d
    if qk_norm and not headwise and ax is not None:
        raise NotImplementedError(
            "q/k normalisation spans every head: it does not compose with "
            "head-split tensor parallelism yet")
    norm = _rms_norm_1p if getattr(cfg, "zero_centered_norm", False) \
        else _rms_norm
    gate = None
    with jax.named_scope("attn/qkv"):
        q = qmm(xa, lp_attn["q_proj"]["kernel"], dt)
        if getattr(cfg, "attn_output_gate", False):
            q, gate = jnp.split(q.reshape(-1, h, 2 * d), 2, axis=-1)
        if qk_norm and not headwise:
            q = _rms_norm(q, lp_attn["q_norm"]["scale"], cfg.rms_norm_eps)
        if getattr(cfg, "query_scale", None) is not None:
            q = (q.astype(jnp.float32) * cfg.query_scale).astype(q.dtype)
        q = q.reshape(-1, h, d)
        k = qmm(xa, lp_attn["k_proj"]["kernel"], dt)
        if qk_norm and not headwise:
            k = _rms_norm(k, lp_attn["k_norm"]["scale"], cfg.rms_norm_eps)
        k = k.reshape(-1, hkv, d)
        if headwise:
            q = norm(q, lp_attn["q_norm"]["scale"], cfg.rms_norm_eps)
            k = norm(k, lp_attn["k_norm"]["scale"], cfg.rms_norm_eps)
        v = qmm(xa, lp_attn["v_proj"]["kernel"], dt).reshape(-1, hkv, d)
    with jax.named_scope("attn/rope_insert"):
        q, k_pool, v_pool, k_scale, v_scale, new_cache = _rope_insert(
            q, k, v, cos, sin, layer_cache, kv_dest)
    out = _paged_attention(q, k_pool, v_pool, batch, block_size,
                           window=cfg.sliding_window,
                           prefill_tile=prefill_tile,
                           decode_mode=decode_mode, verify_k=verify_k,
                           k_scale=k_scale, v_scale=v_scale)
    with jax.named_scope("attn/out_proj"):
        if gate is not None:
            out = (out.astype(jnp.float32) * jax.nn.sigmoid(
                gate.astype(jnp.float32))).astype(out.dtype)
        out = qmm(out.reshape(-1, h * d), lp_attn["o_proj"]["kernel"], dt)
        if ax is not None:
            out = jax.lax.psum(out, ax)               # row-parallel attn-out
    return out, new_cache


def _rope_insert(q, k, v, cos, sin, layer_cache, kv_dest):
    """Rotary on q and k (``cos`` None: a layer without a positional
    embedding), then the paged-KV scatter of this step's k and v (device
    scope ``attn/rope_insert``).  Returns ``(q, k_pool, v_pool, k_scale,
    v_scale, new_layer_cache)``; the scales are None on a float pool."""
    # apply_rotary broadcasts over [T, H, D] with cos/sin [T, 1, D/2]
    rot = q.shape[-1] if cos is None else 2 * cos.shape[-1]
    if rot < q.shape[-1]:           # partial rotary: the first dims only
        q = jnp.concatenate([apply_rotary(q[..., :rot], cos, sin),
                             q[..., rot:]], axis=-1)
        k = jnp.concatenate([apply_rotary(k[..., :rot], cos, sin),
                             k[..., rot:]], axis=-1)
    elif cos is not None:
        q = apply_rotary(q, cos, sin)
        k = apply_rotary(k, cos, sin)
    # dtype-polymorphic pool (static branch: the leaf dtype is known at
    # trace time).  int8 mode quantizes ON INSERT — payload + per-row/
    # per-head scale scatter in the same step, so the cache is always
    # self-describing and every downstream reader (kernels, COW copy,
    # host spool, disaggregated handoff) sees one consistent record.
    if layer_cache["k"].dtype == jnp.int8:
        from deepspeed_tpu.inference.v2.ragged.kv_cache import quantize_kv

        kq, ks = quantize_kv(k)
        vq, vs = quantize_kv(v)
        k_pool = layer_cache["k"].at[kv_dest].set(kq)
        v_pool = layer_cache["v"].at[kv_dest].set(vq)
        k_scale = layer_cache["k_scale"].at[kv_dest].set(ks)
        v_scale = layer_cache["v_scale"].at[kv_dest].set(vs)
        return q, k_pool, v_pool, k_scale, v_scale, {
            "k": k_pool, "v": v_pool, "k_scale": k_scale, "v_scale": v_scale}
    k_pool, v_pool = insert_kv(layer_cache, kv_dest, k, v)
    return q, k_pool, v_pool, None, None, {"k": k_pool, "v": v_pool}


def insert_kv(layer_cache, kv_dest, k, v):
    """The paged-KV scatter of ``k`` and ``v`` ``[T, Hkv, D]`` into a float
    layer's pools; ``(k_pool, v_pool)``.  In the flat row [rows, Hkv*D] a
    token's heads stand side by side and are written as one row, addressed
    as (sublane tile, row of the tile) in the pool's ``[rows / 16, 16,
    Hkv*D]`` view (16 bf16 rows a tile; a free split of the leading
    dimension).  Why not ``pool.at[kv_dest]``: on a pool of a few MB (a
    test-sized engine, a TP shard) XLA's TPU scatter takes that form through
    a sort of the indices, and a step program holding that scatter never
    returned on the chip once another engine had run in the process (PERF.md
    section 6, PR 41: calls 6-11); the two-index form takes no sort there,
    and on a pool of a cell's size XLA folds it back into the same row
    scatter, to the instruction."""
    def put(pool, x):
        if pool.ndim != 2:
            return pool.at[kv_dest].set(x.astype(pool.dtype))
        rows, lanes = pool.shape
        tile = math.gcd(rows, 32 // pool.dtype.itemsize)
        return pool.reshape(rows // tile, tile, lanes).at[
            jax.lax.div(kv_dest, tile), jax.lax.rem(kv_dest, tile)].set(
                x.reshape(-1, lanes).astype(pool.dtype)).reshape(rows, lanes)
    return put(layer_cache["k"], k), put(layer_cache["v"], v)


def _rotary(positions, head_dim, theta):
    """positions: [T] -> (cos, sin): [T, 1, D/2] fp32."""
    inv_freq = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                                / head_dim))
    angles = positions[:, None].astype(jnp.float32) * inv_freq   # [T, D/2]
    return jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]


def two_segment_case(tight_pool: bool = False, d: int = 128):
    """One two-segment batch through the kernel route (compiled on the
    chip, interpreted off it) and through the XLA composition: ``(got,
    want, mask of the real rows)``.  Shared with tools/kernel_selftest.py.
    ``tight_pool`` sizes the pool at the table extent instead of over
    twice it: the single-token rows take the decode walk either way.  The
    pool row is flat, [rows, Hkv*D], as ``BlockedKVCache`` stores it."""
    import numpy as np

    bs, S, B, tile, h, hkv = 128, 4, 4, 128, 32, 8
    nb = S * B + 1 if tight_pool else 2 * S * B + 2
    rng = np.random.default_rng(21)
    pool = lambda: jnp.asarray(
        rng.standard_normal((nb * bs, hkv * d)).astype(np.float32),
        jnp.bfloat16)
    kp, vp = pool(), pool()
    tables = jnp.arange(1, S * B + 1, dtype=jnp.int32).reshape(S, B)
    T = S + 3 * tile
    slot = np.zeros((T,), np.int32)
    pos = np.full((T,), -1, np.int32)
    slot[0:2], pos[0:2] = (2, 0), (317, 200)       # two decodes, two pads
    slot[S:S + 150], pos[S:S + 150] = 1, np.arange(100, 250)  # tile + tail
    slot[S + 256:S + 384], pos[S + 256:S + 384] = 3, np.arange(0, 128)
    q = jnp.asarray(rng.standard_normal((T, h, d)).astype(np.float32),
                    jnp.bfloat16)
    batch = {"block_tables": tables, "token_slot": jnp.asarray(slot),
             "token_pos": jnp.asarray(pos)}
    got = _paged_attention(q, kp, vp, batch, bs, use_kernel=True,
                           prefill_tile=tile)
    want = _paged_attention(q, kp, vp, batch, bs, use_kernel=False)
    return got, want, pos >= 0


@pallas_kernel_case(
    "paged_two_segment",
    note="a mixed tick's batch at Mistral's head counts (32q/8kv, d=128) "
         "on the flat pool row [rows, 1024] through "
         "_paged_attention: 4 single-token rows "
         "(slots in no order, two pads at position -1) take the decode "
         "walk, every KV head in one pair of dots, the tile-aligned chunks "
         "behind them (one with a sub-tile tail) the tiled prefill kernel")
def _dslint_paged_two_segment_case():
    two_segment_case()


@pallas_kernel_case(
    "paged_two_segment_d64",
    note="the same mixed tick at 64-wide heads (32q/8kv), pool row "
         "[rows, 512]: the single-token rows take the decode walk with "
         "two KV heads to a 128-lane tile (the "
         "queries zero-padded into their half), the chunks the tiled "
         "kernel's per-head slices at 64-lane offsets")
def _dslint_paged_two_segment_d64_case():
    two_segment_case(d=64)
