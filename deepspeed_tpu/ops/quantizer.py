"""Groupwise quantization kernels (role of the reference's CUDA quantization
library: csrc/quantization/{quantize,dequantize,quant_reduce,
swizzled_quantize,quantize_intX,fake_quantizer}.cu + pt_binding.cpp, exposed
through deepspeed/ops/quantizer and op_builder/quantizer.py).

Semantics match the reference kernels:

* **symmetric** int8/int4: per-group scale = max(|x|) / q_range, no offset
  (quantize.cu ``launch_quant`` symmetric path).
* **asymmetric**: per-group scale = (max - min) / (2^bits - 1) and offset =
  min, so the full signed range is used (asymmetric path + quantize_intX.cu).
* **stochastic rounding** variants (sr_quantize, fake_quantizer.cu SR path).
* **quantized_reduce** — dequant → mean over the reduce dimension → requant,
  the ZeRO++ gradient reduce primitive (quant_reduce.cu
  ``launch_dequant_reduce``).
* **swizzle_quant** — groupwise quant with a node-major pre-permute so each
  secondary-partition shard is contiguous for hierarchical all-gather
  (swizzled_quantize.cu). On TPU the permute is a reshape/transpose XLA
  fuses into the surrounding collective.

int4 values are packed two-per-int8 (pack_int4/unpack_int4) so communication
volume actually halves; compute happens unpacked on the VPU.

A Pallas kernel (``_quantize_pallas``) covers the hot symmetric-int8 path on
TPU; everywhere else the jnp composition is a single XLA fusion anyway.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from deepspeed_tpu.utils.platform import kernel_names, on_tpu

__all__ = [
    "quantize", "dequantize", "fake_quantize", "stochastic_quantize",
    "quantized_reduce", "swizzle_quant", "pack_int4", "unpack_int4",
    "QuantizerBuilder",
]


def _q_range(num_bits: int, symmetric: bool) -> Tuple[float, float]:
    if symmetric:
        q = float(2 ** (num_bits - 1) - 1)          # 127 / 7
        return -q, q
    return 0.0, float(2 ** num_bits - 1)            # 0..255 / 0..15


def _group(x: jnp.ndarray, num_groups: int) -> jnp.ndarray:
    n = x.size
    if n % num_groups != 0:
        raise ValueError(f"size {n} not divisible by num_groups {num_groups}")
    return x.reshape(num_groups, n // num_groups)


def quantize(x: jnp.ndarray, num_groups: int, num_bits: int = 8,
             symmetric: bool = True,
             ) -> Tuple[jnp.ndarray, jnp.ndarray, Optional[jnp.ndarray]]:
    """Groupwise quantize ``x`` → (q, scale, offset).

    q is int8 (int4 values occupy the low nibble range, use :func:`pack_int4`
    to halve the wire size). scale/offset are fp32 of shape [num_groups].
    offset is None for symmetric quantization.
    """
    g = _group(x, num_groups).astype(jnp.float32)
    lo, hi = _q_range(num_bits, symmetric)
    if symmetric:
        absmax = jnp.max(jnp.abs(g), axis=1, keepdims=True)
        scale = jnp.where(absmax > 0, absmax / hi, 1.0)
        q = jnp.clip(jnp.round(g / scale), lo, hi).astype(jnp.int8)
        return q, scale[:, 0], None
    gmin = jnp.min(g, axis=1, keepdims=True)
    gmax = jnp.max(g, axis=1, keepdims=True)
    scale = jnp.where(gmax > gmin, (gmax - gmin) / hi, 1.0)
    q = jnp.clip(jnp.round((g - gmin) / scale), lo, hi)
    # asymmetric values stored unsigned-in-int8 (uint8 semantics, like the
    # reference's int8 buffer reinterpret)
    q = (q - 128.0).astype(jnp.int8) if num_bits == 8 else q.astype(jnp.int8)
    return q, scale[:, 0], gmin[:, 0]


def dequantize(q: jnp.ndarray, scale: jnp.ndarray,
               offset: Optional[jnp.ndarray] = None, num_bits: int = 8,
               dtype=jnp.float32) -> jnp.ndarray:
    """Inverse of :func:`quantize`; returns shape [num_groups, group_size]."""
    g = q.astype(jnp.float32)
    if offset is None:                                # symmetric
        out = g * scale[:, None]
    else:
        if num_bits == 8:
            g = g + 128.0
        out = g * scale[:, None] + offset[:, None]
    return out.astype(dtype)


def fake_quantize(x: jnp.ndarray, num_groups: int, num_bits: int = 8,
                  symmetric: bool = True) -> jnp.ndarray:
    """Quantize-dequantize in place (reference ``ds_quantize`` /
    fake_quantizer.cu) — the QAT forward. Shape-preserving."""
    q, s, o = quantize(x, num_groups, num_bits, symmetric)
    return dequantize(q, s, o, num_bits, x.dtype).reshape(x.shape)


def stochastic_quantize(x: jnp.ndarray, num_groups: int, key: jax.Array,
                        num_bits: int = 8, symmetric: bool = True,
                        ) -> Tuple[jnp.ndarray, jnp.ndarray,
                                   Optional[jnp.ndarray]]:
    """Stochastic-rounding variant (reference ``ds_sr_quantize``): round up
    with probability equal to the fractional part, making the quantizer
    unbiased — used for gradient compression."""
    g = _group(x, num_groups).astype(jnp.float32)
    lo, hi = _q_range(num_bits, symmetric)
    if symmetric:
        absmax = jnp.max(jnp.abs(g), axis=1, keepdims=True)
        scale = jnp.where(absmax > 0, absmax / hi, 1.0)
        v = g / scale
        off = None
    else:
        gmin = jnp.min(g, axis=1, keepdims=True)
        gmax = jnp.max(g, axis=1, keepdims=True)
        scale = jnp.where(gmax > gmin, (gmax - gmin) / hi, 1.0)
        v = (g - gmin) / scale
        off = gmin[:, 0]
    floor = jnp.floor(v)
    frac = v - floor
    rnd = jax.random.uniform(key, v.shape)
    q = jnp.clip(floor + (rnd < frac), lo, hi)
    if off is not None and num_bits == 8:
        q = q - 128.0
    return q.astype(jnp.int8), scale[:, 0], off


def quantized_reduce(q: jnp.ndarray, scale: jnp.ndarray, num_ranks: int,
                     num_bits: int = 8) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Dequantize ``num_ranks`` stacked quantized chunks, average, requantize
    (reference quant_reduce.cu ``launch_dequant_reduce`` — the inner op of
    ZeRO++'s all-to-all quantized gradient reduce).

    q: int8 [num_ranks, num_groups, group], scale: [num_ranks, num_groups].
    Returns (q_out [num_groups, group], scale_out [num_groups]).
    """
    full = q.astype(jnp.float32) * scale[:, :, None]
    mean = jnp.mean(full, axis=0)
    _, hi = _q_range(num_bits, True)
    absmax = jnp.max(jnp.abs(mean), axis=1, keepdims=True)
    out_scale = jnp.where(absmax > 0, absmax / hi, 1.0)
    q_out = jnp.clip(jnp.round(mean / out_scale), -hi, hi).astype(jnp.int8)
    return q_out, out_scale[:, 0]


def swizzle_quant(x: jnp.ndarray, num_groups: int, pipeline_size: int,
                  num_bits: int = 8,
                  ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Quantize with a node-major swizzle (reference swizzled_quantize.cu):
    element i of every pipeline chunk is made contiguous so the hierarchical
    (intra-node then inter-node) all-gather reads contiguous shards.

    Returns (q [num_groups, group], scale [num_groups]) over the swizzled
    layout; :func:`unswizzle` is a reshape-transpose the caller applies after
    the gather.
    """
    flat = x.reshape(-1)
    if flat.size % pipeline_size != 0:
        raise ValueError("size not divisible by pipeline_size")
    sw = flat.reshape(pipeline_size, -1).T.reshape(-1)
    q, s, _ = quantize(sw, num_groups, num_bits, True)
    return q, s


def unswizzle(x: jnp.ndarray, pipeline_size: int) -> jnp.ndarray:
    flat = x.reshape(-1)
    return flat.reshape(-1, pipeline_size).T.reshape(-1)


def pack_int4(q: jnp.ndarray) -> jnp.ndarray:
    """Pack int4 values (stored in int8, range [-8,7] or [0,15]) two per
    byte along the last axis (quantize_intX.cu layout)."""
    if q.shape[-1] % 2 != 0:
        raise ValueError("last dim must be even to pack int4")
    lo = q[..., 0::2].astype(jnp.uint8) & 0xF
    hi = (q[..., 1::2].astype(jnp.uint8) & 0xF) << 4
    return (lo | hi).astype(jnp.int8)


def unpack_int4(p: jnp.ndarray, signed: bool = True) -> jnp.ndarray:
    u = p.astype(jnp.uint8)
    lo = (u & 0xF).astype(jnp.int8)
    hi = ((u >> 4) & 0xF).astype(jnp.int8)
    out = jnp.stack([lo, hi], axis=-1).reshape(*p.shape[:-1],
                                               p.shape[-1] * 2)
    if signed:  # sign-extend nibble
        out = jnp.where(out > 7, out - 16, out)
    return out


# ------------------------------------------------------------------ #
# Pallas hot path: symmetric int8 groupwise quantize.
# ------------------------------------------------------------------ #

def _quantize_kernel(x_ref, q_ref, s_ref):
    import jax.numpy as jnp  # noqa: F811 (kernel-local)
    x = x_ref[...].astype(jnp.float32)
    absmax = jnp.max(jnp.abs(x), axis=1, keepdims=True)
    scale = jnp.where(absmax > 0, absmax / 127.0, 1.0)
    q_ref[...] = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
    s_ref[...] = scale[:, 0]


def _quantize_kernel_call(g: jnp.ndarray):
    """``pallas_call`` plumbing for the symmetric int8 groupwise
    quantize (factored out of :func:`quantize_pallas` so the dslint
    contract checker can reach it off-TPU). ``g``: [ng, group_size]."""
    from jax.experimental import pallas as pl

    ng, gs = g.shape
    # int8 output tiles pack 32 sublanes: prefer a 32-row block so the
    # q_ref writes stay tile-aligned (8-row blocks forced a Mosaic
    # relayout of the int8 output)
    block_g = 32 if ng % 32 == 0 else (8 if ng % 8 == 0 else 1)
    out = pl.pallas_call(
        _quantize_kernel,
        grid=(ng // block_g,),
        in_specs=[pl.BlockSpec((block_g, gs), lambda i: (i, 0))],
        out_specs=[pl.BlockSpec((block_g, gs), lambda i: (i, 0)),
                   pl.BlockSpec((block_g,), lambda i: (i,))],
        out_shape=[jax.ShapeDtypeStruct((ng, gs), jnp.int8),
                   jax.ShapeDtypeStruct((ng,), jnp.float32)],
        **kernel_names(_quantize_kernel),
    )(g)
    return out[0], out[1]


@functools.partial(jax.jit, static_argnums=(1,))
def quantize_pallas(x: jnp.ndarray, num_groups: int):
    """Pallas symmetric int8 quantize; one grid step per group block.

    Falls back to :func:`quantize` off-TPU (the jnp form is one XLA fusion
    there anyway).
    """
    if not on_tpu():
        q, s, _ = quantize(x, num_groups, 8, True)
        return q, s
    return _quantize_kernel_call(_group(x, num_groups))


# ------------------------------------------------------------------ #
# dslint contract-checker registration (see analysis/pallas_lint.py):
# runs only under the checker's capture context, never in production.
# ------------------------------------------------------------------ #
from deepspeed_tpu.analysis.registry import pallas_kernel_case  # noqa: E402


@pallas_kernel_case("quantizer_int8",
                    note="symmetric int8 groupwise quantize hot path")
def _dslint_quantizer_case():
    import numpy as np

    x = jnp.asarray(np.linspace(-1.0, 1.0, 64 * 512, dtype=np.float32))
    _quantize_kernel_call(_group(x, 64))


class QuantizerBuilder:
    """op_builder surface (reference op_builder/quantizer.py)."""

    NAME = "quantizer"

    def load(self):
        import deepspeed_tpu.ops.quantizer as m
        return m

    def is_compatible(self) -> bool:
        return True
