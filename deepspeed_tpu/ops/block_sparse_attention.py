"""Pallas block-sparse attention — the splash-attention analog of the
reference's Triton kernels (deepspeed/ops/sparse_attention/trsrc/
matmul.tr sdd/dsd + softmax.tr; SURVEY §2.8).

The point of sparse attention is SKIPPED COMPUTE, not masked compute: the
dense-masked composition in :mod:`ops.sparse_attention` still does O(S²)
work. Here the block layout drives the kernels:

* a tile-level any-mask (``tile_any[h, IQ, IK]``, host-precomputed from
  the layout) rides in scalar-prefetch SMEM and predicates each grid step
  with ``pl.when`` — fully-empty tiles do no MXU/VPU work at all;
* the layout cells covering a live tile stream in as a normal blocked
  input and expand to the element mask with broadcasts (no gathers);
* forward + both backward kernels share the structure of
  :mod:`ops.flash_attention` (online softmax over the k-tile axis,
  lse-based recompute backward), so autodiff sees one ``custom_vjp``.

Layout granularity (``SparsityConfig.block``, typically 16-32) is finer
than the MXU-efficient tile (128+): a kernel tile covers a rectangle of
layout cells and runs if ANY of them is set.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.ops.flash_attention import NEG_INF
from deepspeed_tpu.utils.platform import kernel_names, on_tpu


def _pick_tile(s: int, block: int, target: int = 256) -> int:
    """Largest multiple of ``block`` that divides s, capped at target."""
    best = block
    t = block
    while t <= min(s, target):
        if s % t == 0:
            best = t
        t += block
    return best


def _tile_any(layout: np.ndarray, tq: int, tk: int, block: int
              ) -> np.ndarray:
    """[h, nc, nc] cells -> [h, S/tq, S/tk] int32 tile-level any-mask."""
    h, nc, _ = layout.shape
    cq, ck = tq // block, tk // block
    m = layout.reshape(h, nc // cq, cq, nc // ck, ck)
    return m.any(axis=(2, 4)).astype(np.int32)


def _cell_mask(cells, block: int, bq: int, bk: int):
    """[cq, ck] int32 cells -> [bq, bk] bool element mask.

    Expansion by MATMUL against iota-built 0/1 expansion matrices
    (``Eq[r, i] = [r // block == i]``): Mosaic supports neither sub-32-bit
    broadcasts nor the interleaving (cq, block, ck, block) -> (bq, bk)
    shape cast, but two tiny fp32 dots lower cleanly everywhere."""
    cq, ck = cells.shape
    inv = jnp.float32(1.0 / block)
    # fp32 iotas + cmpf: Mosaic can't legalize the int cmpi here
    f32iota = lambda shape, dim: jax.lax.broadcasted_iota(
        jnp.int32, shape, dim).astype(jnp.float32)
    eq = jnp.where(jnp.floor(f32iota((bq, cq), 0) * inv)
                   == f32iota((bq, cq), 1), 1.0, 0.0)
    ek = jnp.where(jnp.floor(f32iota((ck, bk), 1) * inv)
                   == f32iota((ck, bk), 0), 1.0, 0.0)
    m = jax.lax.dot(eq, jax.lax.dot(cells.astype(jnp.float32), ek,
                                    preferred_element_type=jnp.float32),
                    preferred_element_type=jnp.float32)
    return m > 0


# ===================================================================== #
# Forward
# ===================================================================== #
def _fwd_kernel(tile_any, cells_ref, q_ref, k_ref, v_ref, o_ref, lse_ref,
                acc_ref, m_ref, l_ref, *, block, block_q, block_k,
                num_k_tiles):
    h = pl.program_id(1)
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    @pl.when(tile_any[h, iq, ik] != 0)
    def _():
        q = q_ref[0, 0]                               # [bq, d] (pre-scaled)
        kb = k_ref[0, 0]                              # [bk, d]
        s = jax.lax.dot_general(
            q, kb, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        keep = _cell_mask(cells_ref[0, 0, 0], block, block_q, block_k)
        s = jnp.where(keep, s, NEG_INF)
        m_prev = m_ref[:, :1]
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)
        p = jnp.where(keep, p, 0.0)   # exp(NEG_INF-m) underflows, but an
        # all-masked ROW has m_new == NEG_INF and exp(0) == 1 — zero it
        corr = jnp.exp(m_prev - m_new)
        l_new = l_ref[:, :1] * corr + jnp.sum(p, axis=1, keepdims=True)
        vb = v_ref[0, 0]
        acc_ref[:] = acc_ref[:] * corr + jax.lax.dot_general(
            p.astype(vb.dtype), vb, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[:] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(ik == num_k_tiles - 1)
    def _():
        l = l_ref[:, :1]
        safe_l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_ref[:] / safe_l).astype(o_ref.dtype)
        lse_ref[0, 0] = jnp.broadcast_to(
            m_ref[:, :1] + jnp.log(safe_l), lse_ref[0, 0].shape)


# ===================================================================== #
# Backward
# ===================================================================== #
def _bwd_dq_kernel(tile_any, cells_ref, q_ref, k_ref, v_ref, do_ref,
                   lse_ref, delta_ref, dq_ref, dq_acc, *, block, block_q,
                   block_k, num_k_tiles, scale):
    h = pl.program_id(1)
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    @pl.when(tile_any[h, iq, ik] != 0)
    def _():
        q = q_ref[0, 0]
        kb = k_ref[0, 0]
        vb = v_ref[0, 0]
        do = do_ref[0, 0]
        lse = lse_ref[0, 0][:, :1]
        delta = delta_ref[0, 0][:, :1]
        s = jax.lax.dot_general(q, kb, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        keep = _cell_mask(cells_ref[0, 0, 0], block, block_q, block_k)
        p = jnp.where(keep, jnp.exp(s - lse), 0.0)
        dp = jax.lax.dot_general(do, vb, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = (p * (dp - delta)).astype(kb.dtype)
        dq_acc[:] = dq_acc[:] + jax.lax.dot_general(
            ds, kb, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(ik == num_k_tiles - 1)
    def _():
        dq_ref[0, 0] = (dq_acc[:] * scale).astype(dq_ref.dtype)


def _bwd_dkv_kernel(tile_any, cells_ref, q_ref, k_ref, v_ref, do_ref,
                    lse_ref, delta_ref, dk_ref, dv_ref, dk_acc, dv_acc, *,
                    block, block_q, block_k, num_q_tiles):
    h = pl.program_id(1)
    ik = pl.program_id(2)
    iq = pl.program_id(3)

    @pl.when(iq == 0)
    def _():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    @pl.when(tile_any[h, iq, ik] != 0)
    def _():
        q = q_ref[0, 0]
        kb = k_ref[0, 0]
        vb = v_ref[0, 0]
        do = do_ref[0, 0]
        lse = lse_ref[0, 0][:, :1]
        delta = delta_ref[0, 0][:, :1]
        s = jax.lax.dot_general(q, kb, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        keep = _cell_mask(cells_ref[0, 0, 0], block, block_q, block_k)
        p = jnp.where(keep, jnp.exp(s - lse), 0.0)
        pb = p.astype(do.dtype)
        dv_acc[:] = dv_acc[:] + jax.lax.dot_general(
            pb, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, vb, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = (p * (dp - delta)).astype(q.dtype)
        dk_acc[:] = dk_acc[:] + jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(iq == num_q_tiles - 1)
    def _():
        dk_ref[0, 0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[:].astype(dv_ref.dtype)


# ===================================================================== #
# pallas_call plumbing
# ===================================================================== #
def _specs(block, block_q, block_k, d, cq, ck, *, kv_major: bool):
    """(in_specs, q_idx, k_idx) for the (b, h, iq, ik)-style grids."""
    # Index maps receive the scalar-prefetch ref (tile_any) as a trailing
    # arg. DEAD tiles clamp their big-block DMA index to 0: a run of dead
    # tiles then re-names the same block and the Pallas pipeline elides
    # the transfers — without this, skipped tiles still paid full KV
    # bandwidth and the kernel was DMA-bound at low density.
    if kv_major:  # grid (b, h, ik, iq) — the iq-indexed blocks vary
        def q_idx(b_, h_, ik, iq, ta):
            return (b_, h_,
                    jnp.where(ta[h_, iq, ik] != 0, iq, 0), 0)

        k_idx = lambda b_, h_, ik, iq, *_: (b_, h_, ik, 0)
        c_idx = lambda b_, h_, ik, iq, *_: (h_, iq, ik, 0, 0)

        def l_idx(b_, h_, ik, iq, ta):
            return (b_, h_,
                    jnp.where(ta[h_, iq, ik] != 0, iq, 0), 0)
    else:         # grid (b, h, iq, ik) — the ik-indexed blocks vary
        q_idx = lambda b_, h_, iq, ik, *_: (b_, h_, iq, 0)

        def k_idx(b_, h_, iq, ik, ta):
            return (b_, h_,
                    jnp.where(ta[h_, iq, ik] != 0, ik, 0), 0)

        c_idx = lambda b_, h_, iq, ik, *_: (h_, iq, ik, 0, 0)
        l_idx = lambda b_, h_, iq, ik, *_: (b_, h_, iq, 0)
    cells = pl.BlockSpec((1, 1, 1, cq, ck), c_idx)
    qs = pl.BlockSpec((1, 1, block_q, d), q_idx)
    ks = pl.BlockSpec((1, 1, block_k, d), k_idx)
    ls = pl.BlockSpec((1, 1, block_q, 8), l_idx)
    return cells, qs, ks, ls


def _fwd(q, k, v, cells, tile_any, *, block, block_q, block_k, interpret):
    b, h, s, d = q.shape
    nq, nk = s // block_q, s // block_k
    cq, ck = block_q // block, block_k // block
    cells_spec, qs, ks, ls = _specs(block, block_q, block_k, d, cq, ck,
                                    kv_major=False)
    kernel = functools.partial(_fwd_kernel, block=block, block_q=block_q,
                               block_k=block_k, num_k_tiles=nk)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, h, nq, nk),
        in_specs=[cells_spec, qs, ks, ks],
        out_specs=[qs, ls],
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((b, h, s, d), q.dtype),
                   jax.ShapeDtypeStruct((b, h, s, 8), jnp.float32)],
        interpret=interpret,
        **kernel_names(kernel),
    )(tile_any, cells, q, k, v)


def _bwd(res, g, *, block, block_q, block_k, scale, interpret):
    q, k, v, o, lse, cells, tile_any = res
    do = g[0] if isinstance(g, tuple) else g
    b, h, s, d = q.shape
    nq, nk = s // block_q, s // block_k
    cq, ck = block_q // block, block_k // block

    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    delta = jnp.broadcast_to(delta[..., None], delta.shape + (8,))

    cells_spec, qs, ks, ls = _specs(block, block_q, block_k, d, cq, ck,
                                    kv_major=False)
    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, block=block, block_q=block_q,
                          block_k=block_k, num_k_tiles=nk, scale=scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, h, nq, nk),
            in_specs=[cells_spec, qs, ks, ks, qs, ls, ls],
            out_specs=qs,
            scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        interpret=interpret,
        **kernel_names(_bwd_dq_kernel),
    )(tile_any, cells, q, k, v, do, lse, delta)

    cells_spec, qs, ks, ls = _specs(block, block_q, block_k, d, cq, ck,
                                    kv_major=True)
    kvs = pl.BlockSpec((1, 1, block_k, d),
                       lambda b_, h_, ik, iq, *_: (b_, h_, ik, 0))
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, block=block, block_q=block_q,
                          block_k=block_k, num_q_tiles=nq),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, h, nk, nq),
            in_specs=[cells_spec, qs, ks, ks, qs, ls, ls],
            out_specs=[kvs, kvs],
            scratch_shapes=[pltpu.VMEM((block_k, d), jnp.float32),
                            pltpu.VMEM((block_k, d), jnp.float32)]),
        out_shape=[jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        interpret=interpret,
        **kernel_names(_bwd_dkv_kernel),
    )(tile_any, cells, q, k, v, do, lse, delta)
    return dq, dk.astype(k.dtype), dv.astype(v.dtype)


# ===================================================================== #
# Public entry
# ===================================================================== #
@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9))
def _bs_attn(q, k, v, cells, tile_any, block, block_q, block_k, scale,
             interpret):
    # scale folded into q INSIDE the vjp: the dq kernel applies the final
    # * scale itself (dk needs none — the residual saves the scaled q)
    qs = (q.astype(jnp.float32) * scale).astype(q.dtype)
    o, _ = _fwd(qs, k, v, cells, tile_any, block=block, block_q=block_q,
                block_k=block_k, interpret=interpret)
    return o


def _bs_fwd(q, k, v, cells, tile_any, block, block_q, block_k, scale,
            interpret):
    qs = (q.astype(jnp.float32) * scale).astype(q.dtype)
    o, lse = _fwd(qs, k, v, cells, tile_any, block=block, block_q=block_q,
                  block_k=block_k, interpret=interpret)
    return o, (qs, k, v, o, lse, cells, tile_any)


def _bs_bwd(block, block_q, block_k, scale, interpret, res, g):
    dq, dk, dv = _bwd(res, g, block=block, block_q=block_q,
                      block_k=block_k, scale=scale, interpret=interpret)
    return dq, dk, dv, None, None


_bs_attn.defvjp(_bs_fwd, _bs_bwd)


class BlockSparseLayout:
    """Host-precomputed kernel inputs for one (layout, seq_len)."""

    def __init__(self, layout: np.ndarray, block: int, seq_len: int,
                 tile_q: Optional[int] = None, tile_k: Optional[int] = None):
        h, nc, _ = layout.shape
        if nc * block != seq_len:
            raise ValueError(f"layout {nc}x{block} != seq {seq_len}")
        self.block = block
        self.tile_q = tile_q or _pick_tile(seq_len, block)
        self.tile_k = tile_k or _pick_tile(seq_len, block)
        # tile-major cell layout [h, TQ, TK, cq, ck]: each kernel tile's
        # cells are one contiguous block whose trailing dims EQUAL the
        # block shape (the TPU lowering requires minor block dims to be
        # (8,128)-divisible or exactly the array dims)
        tq_tiles = seq_len // self.tile_q
        tk_tiles = seq_len // self.tile_k
        cq = self.tile_q // block
        ck = self.tile_k // block
        # int32 cells: Mosaic supports neither sub-32-bit minor-dim
        # broadcasts nor uint8 casts; the array is tiny
        cells5 = layout.astype(np.int32).reshape(
            h, tq_tiles, cq, tk_tiles, ck).transpose(0, 1, 3, 2, 4)
        self.cells = jnp.asarray(np.ascontiguousarray(cells5))
        self.tile_any = jnp.asarray(
            _tile_any(layout, self.tile_q, self.tile_k, block))
        self.density = float(layout.mean())

    def tiles_skipped(self) -> Tuple[int, int]:
        ta = np.asarray(self.tile_any)
        return int((ta == 0).sum()), int(ta.size)


def block_sparse_attention(q, k, v, bs_layout: BlockSparseLayout,
                           scale: Optional[float] = None,
                           interpret: Optional[bool] = None):
    """q/k/v: [batch, heads, seq, dim] -> [batch, heads, seq, dim].

    Rows whose layout admits no keys return 0 (the dense-masked reference
    returns a uniform average there; real layouts have no empty rows).
    """
    b, h, s, d = q.shape
    scale = scale if scale is not None else 1.0 / float(np.sqrt(d))
    if interpret is None:
        interpret = not on_tpu()
    return _bs_attn(q, k, v, bs_layout.cells, bs_layout.tile_any,
                    bs_layout.block, bs_layout.tile_q, bs_layout.tile_k,
                    float(scale), bool(interpret))


# ===================================================================== #
# dslint contract-checker registration (see analysis/pallas_lint.py):
# a ~50%-density layout with a guaranteed-live diagonal (every q tile
# row has work, so the dead-tile-clamped output index maps still cover
# every output block), forward + both backward kernels.
# ===================================================================== #
from deepspeed_tpu.analysis.registry import pallas_kernel_case  # noqa: E402


@pallas_kernel_case("block_sparse_attention",
                    note="BigBird-style layout, fwd + dq + dkv kernels")
def _dslint_block_sparse_case():
    h, s, d, blk = 4, 512, 64, 64
    rng = np.random.default_rng(3)
    layout = (rng.random((h, s // blk, s // blk)) < 0.5)
    layout |= np.eye(s // blk, dtype=bool)[None]
    bsl = BlockSparseLayout(layout.astype(np.int32), blk, s)
    mk = lambda: jnp.asarray(
        rng.standard_normal((2, h, s, d)).astype(np.float32), jnp.bfloat16)
    q, k, v = mk(), mk(), mk()
    o = block_sparse_attention(q, k, v, bsl, interpret=True)
    lse = jnp.zeros((2, h, s, 8), jnp.float32)
    _bwd((q, k, v, o, lse, bsl.cells, bsl.tile_any), (o,), block=blk,
         block_q=bsl.tile_q, block_k=bsl.tile_k, scale=0.125,
         interpret=True)
