"""Attention ops (reference: csrc/transformer/*.cu softmax/attention kernels;
inference kernels csrc/transformer/inference/).

``dot_product_attention`` is the single entry point; the ``implementation``
switch selects between the XLA composition (fused well by the compiler) and
the Pallas flash kernel (:mod:`deepspeed_tpu.ops.flash_attention`) once the
shapes warrant it. Layout: [batch, seq, heads, head_dim] throughout.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

# ------------------------------------------------------------------ #
# Attention layout selection
# ------------------------------------------------------------------ #
# "bshd":   [B, S, H, D] boundary; the flash kernels transpose to
#           [B, H, S, D] (the historical path).
# "folded": [B, S, H*D] boundary — the QKV GEMM's native output — consumed
#           directly by the folded Pallas kernels, killing the BSHD<->BHSD
#           transposes (PERFLOG round 5: 13.8 ms of the 86 ms honest-
#           geometry step). Falls back to the bshd path per-call for
#           geometries the folded kernel doesn't support.
# "paired": the folded boundary PLUS head pairing inside the kernel — at
#           head_dim < 128 (the honest GPT-2 d=64 geometry) 128/D heads
#           share one lane-full [block, 128] tile per MXU pass, lifting
#           the half-lane compute ceiling the roofline model names.
#           Falls back per-call to folded (D >= 128 is already
#           lane-full) and from there to bshd.
ATTENTION_LAYOUTS = ("bshd", "folded", "paired")
_DEFAULT_ATTENTION_LAYOUT = "bshd"


def set_default_attention_layout(layout: str) -> None:
    """Process-wide default consulted by models whose config leaves
    ``attention_layout`` unset. The engine calls this from the
    ``attention_layout`` key of the DeepSpeed config (runtime/config.py);
    it must run before the train step is traced (engine __init__ does)."""
    global _DEFAULT_ATTENTION_LAYOUT
    if layout not in ATTENTION_LAYOUTS:
        raise ValueError(
            f"attention_layout must be one of {ATTENTION_LAYOUTS}, "
            f"got {layout!r}")
    _DEFAULT_ATTENTION_LAYOUT = layout


def get_default_attention_layout() -> str:
    return _DEFAULT_ATTENTION_LAYOUT


def resolve_attention_layout(layout: Optional[str]) -> str:
    """A model config's ``attention_layout`` (None -> process default)."""
    if layout is None:
        return _DEFAULT_ATTENTION_LAYOUT
    if layout not in ATTENTION_LAYOUTS:
        raise ValueError(
            f"attention_layout must be one of {ATTENTION_LAYOUTS}, "
            f"got {layout!r}")
    return layout


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
            flash_attention_usable, flash_attention)

        if implementation == "pallas" or flash_attention_usable(
                q, k, v, causal, mask):
            return flash_attention(q, k, v, causal=causal, mask=mask,
                                   scale=scale, window=window)
    return _xla_attention(q, k, v, causal=causal, mask=mask, scale=scale,
                          window=window)


def folded_attention(q, k, v, *, num_heads: int,
                     num_kv_heads: Optional[int] = None,
                     causal: bool = True,
                     scale: Optional[float] = None,
                     window: Optional[int] = None,
                     implementation: str = "auto"):
    """Layout-native attention on the QKV GEMM's folded output.

    q: [B,Sq,H*D]; k/v: [B,Sk,Hkv*D]; returns [B,Sq,H*D]. When the folded
    Pallas kernel applies (``implementation='pallas'`` forces it, 'auto'
    gates on :func:`flash_attention_folded_usable`) nothing is ever
    materialised in [B,S,H,D] — forward or backward. Otherwise the inputs
    are *reshaped* (free — same memory layout) to [B,S,H,D] and routed
    through :func:`dot_product_attention`, so every geometry keeps
    working and only eligible ones take the kernel."""
    hkv = num_kv_heads if num_kv_heads is not None else num_heads
    if implementation in ("auto", "pallas"):
        from deepspeed_tpu.ops.flash_attention import (
            flash_attention_folded, flash_attention_folded_usable)

        if implementation == "pallas" or flash_attention_folded_usable(
                q, k, v, num_heads, hkv, causal, None):
            return flash_attention_folded(
                q, k, v, num_heads=num_heads, num_kv_heads=hkv,
                causal=causal, scale=scale, window=window)
    b, sq, hd = q.shape
    sk = k.shape[1]
    d = hd // num_heads
    out = dot_product_attention(
        q.reshape(b, sq, num_heads, d), k.reshape(b, sk, hkv, d),
        v.reshape(b, sk, hkv, d), causal=causal, scale=scale, window=window,
        implementation="auto" if implementation == "pallas" else implementation)
    return out.reshape(b, sq, hd)


def paired_attention(q, k, v, *, num_heads: int,
                     num_kv_heads: Optional[int] = None,
                     causal: bool = True,
                     scale: Optional[float] = None,
                     window: Optional[int] = None,
                     implementation: str = "auto"):
    """Head-paired attention on the QKV GEMM's folded output.

    q: [B,Sq,H*D]; k/v: [B,Sk,Hkv*D]; returns [B,Sq,H*D].  When head
    pairing applies (D < 128 dividing 128, even head groups) the paired
    Pallas kernel runs every MXU dot at full 128 lanes
    (``implementation='pallas'`` forces it, 'auto' gates on
    :func:`flash_attention_paired_usable`).  Every other geometry —
    D >= 128 (already lane-full) or odd head counts with no pad rule —
    falls through to :func:`folded_attention`, which itself falls back
    to the bshd path, so routing never fails."""
    hkv = num_kv_heads if num_kv_heads is not None else num_heads
    if implementation in ("auto", "pallas"):
        from deepspeed_tpu.ops.flash_attention import (
            flash_attention_paired, flash_attention_paired_usable,
            paired_heads_per_block)

        d = q.shape[-1] // num_heads if q.ndim == 3 and \
            q.shape[-1] % num_heads == 0 else 0
        pairable = d and paired_heads_per_block(num_heads, hkv,
                                                d) is not None
        if pairable and (implementation == "pallas" or
                         flash_attention_paired_usable(
                             q, k, v, num_heads, hkv, causal, None)):
            return flash_attention_paired(
                q, k, v, num_heads=num_heads, num_kv_heads=hkv,
                causal=causal, scale=scale, window=window)
    return folded_attention(q, k, v, num_heads=num_heads, num_kv_heads=hkv,
                            causal=causal, scale=scale, window=window,
                            implementation=implementation)


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
