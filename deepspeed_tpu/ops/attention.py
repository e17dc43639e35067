"""Attention ops (reference: csrc/transformer/*.cu softmax/attention kernels;
inference kernels csrc/transformer/inference/).

``dot_product_attention`` is the single entry point; the ``implementation``
switch selects between the XLA composition (fused well by the compiler) and
the Pallas flash kernels (:mod:`deepspeed_tpu.ops.flash_attention`) once the
shapes warrant it, and the head size picks the kernel family. Layout:
[batch, seq, heads, head_dim] throughout.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp


def dot_product_attention(q, k, v, *, causal: bool = True,
                          mask: Optional[jax.Array] = None,
                          scale: Optional[float] = None,
                          window: Optional[int] = None,
                          bias: Optional[jax.Array] = None,
                          implementation: str = "auto"):
    """q: [B,Sq,H,D]; k/v: [B,Sk,Hkv,D] (GQA when Hkv < H).

    ``window``: Mistral-style causal sliding window — handled natively by
    the flash kernel (out-of-band blocks skipped); the XLA path applies a
    banded mask.  ``bias``: additive attention bias broadcastable to
    [B,H,Sq,Sk] (ALiBi, relative-position) — routes to the XLA path."""
    if bias is not None:
        return _xla_attention(q, k, v, causal=causal, mask=mask,
                              scale=scale, window=window, bias=bias)
    if implementation in ("auto", "pallas"):
        from deepspeed_tpu.ops.flash_attention import (
            flash_attention, flash_attention_folded, flash_attention_usable,
            folded_heads_per_block)

        if implementation == "pallas" or flash_attention_usable(
                q, k, v, causal, mask):
            h, hkv = q.shape[2], k.shape[2]
            if mask is None and folded_heads_per_block(
                    h, hkv, q.shape[-1]) is not None:
                # the head size picks the family: heads narrower than a
                # lane tile, in groups of whole tiles, go to the folded
                # kernels, which read the projections' own [B, S, H*D]
                # rows (the reshape is free), so no [B, S, H, D] <->
                # [B, H, S, D] transpose runs (PERF.md section 6, PR 54:
                # the chip's A/B)
                fold = lambda t: t.reshape(*t.shape[:2], -1)
                return flash_attention_folded(
                    fold(q), fold(k), fold(v), num_heads=h, num_kv_heads=hkv,
                    causal=causal, scale=scale, window=window
                ).reshape(q.shape)
            return flash_attention(q, k, v, causal=causal, mask=mask,
                                   scale=scale, window=window)
    return _xla_attention(q, k, v, causal=causal, mask=mask, scale=scale,
                          window=window)


def _xla_attention(q, k, v, *, causal, mask, scale, window=None, bias=None):
    b, sq, h, d = q.shape
    _, sk, hkv, _ = k.shape
    scale = scale if scale is not None else 1.0 / (d ** 0.5)
    if hkv != h:
        assert h % hkv == 0
        rep = h // hkv
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    # [B,H,Sq,Sk]
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    if bias is not None:
        logits = logits + bias.astype(jnp.float32)
    if causal:
        causal_mask = jnp.tril(jnp.ones((sq, sk), bool), k=sk - sq)
        if window is not None:
            causal_mask &= ~jnp.tril(jnp.ones((sq, sk), bool),
                                     k=sk - sq - window)
        logits = jnp.where(causal_mask[None, None], logits, -1e30)
    if mask is not None:
        logits = jnp.where(mask, logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)
