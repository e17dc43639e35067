"""Gated delta rule — the recurrence of a Gated DeltaNet (linear-attention)
layer over a pool of per-sequence state slots.

Per value head the layer keeps a matrix ``S [dk, dv]`` (float32) for every
live sequence.  One token ``t`` with query ``q_t`` and key ``k_t`` (both
L2-normalised, ``q`` scaled), value ``v_t``, log-decay ``g_t <= 0`` and
write strength ``beta_t`` in (0, 1) does::

    S *= exp(g_t);  d = (v_t - S^T k_t) * beta_t;  S += k_t d^T;  o_t = S^T q_t

The states live in a slot pool ``[slots + 1, H, dk, dv]`` owned by the
serving engine's state manager (``inference/v2/ragged/state_pool.py``); the
last slot is scratch, where pad rows write.  Two entry points, one for each
segment of a ragged batch (``RaggedBatchWrapper.set_alignment``):

* :func:`gdn_step` — rows of one token each (a decode step, the
  single-token segment): one read and one write of each row's slot.
* :func:`gdn_chunk` — the tile segment: every ``tile`` rows belong to one
  sequence, tiles of one sequence follow each other in position order.  The
  recurrence is computed in its chunked (WY) form, ``chunk`` tokens at a
  time: inside a chunk the ``chunk x chunk`` unit-lower-triangular system is
  inverted by block doubling (``_tri_inverse``: exact block forward
  substitution, log2(chunk) levels of two matmuls) and the state enters
  through three matmuls a chunk.

Each has a Mosaic kernel (the TPU path; ``interpret=True`` in tests) and an
XLA composition of the same mathematics (``*_reference``: the path off the
TPU and the parity oracle, as ``gmm_reference`` is for the grouped GEMM).
Pad rows carry ``g = 0`` and ``beta = 0`` (the caller masks them), which
leaves a state exactly as it was; ``reset`` zeroes a slot before its first
token (a sequence whose first position is 0).  Everything is float32.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.utils.platform import kernel_names, on_tpu

F32 = jnp.float32
_HI = jax.lax.Precision.HIGHEST

#: tokens of one chunk of the WY form (the published implementation's)
CHUNK = 64


def _dot(a, b, dims=(((1,), (0,)), ((), ()))):
    return jax.lax.dot_general(a, b, dims, precision=_HI,
                               preferred_element_type=F32)


def _tri_inverse(a):
    """``(I + a)^-1`` for ``a [..., C, C]`` strictly lower triangular, C a
    power of two.  Block doubling: with ``T`` holding the inverses of the
    diagonal blocks of size ``b``, the lower-left block of each ``2b`` block
    of the inverse is ``-T22 a21 T11``; as ``T`` is block diagonal that is
    ``T - T m T`` with ``m`` the masked ``a21`` blocks.  No power of ``a``
    is ever formed, so nothing cancels (a Neumann series would)."""
    c = a.shape[-1]
    i = jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
    j = jax.lax.broadcasted_iota(jnp.int32, (c, c), 1)
    t = jnp.broadcast_to((i == j).astype(F32), a.shape)
    b = 1
    while b < c:
        low = ((i // (2 * b)) == (j // (2 * b))) & ((i // b) % 2 == 1) \
            & ((j // b) % 2 == 0)
        m = jnp.where(low, a, 0.0)
        t = t - jnp.matmul(jnp.matmul(t, m, precision=_HI), t, precision=_HI)
        b *= 2
    return t


# --------------------------------------------------------------------- #
# XLA compositions (off-TPU path, parity oracle)
# --------------------------------------------------------------------- #
def gdn_step_reference(pool, q, k, v, g, beta, slots, reset):
    """One token a row.  pool [N, H, dk, dv]; q, k [S, H, dk]; v [S, H, dv];
    g, beta [S, H]; slots [S] int32; reset [S] bool.  Returns
    ``(o [S, H, dv], new pool)``."""
    s0 = pool[slots] * jnp.where(reset, 0.0, 1.0)[:, None, None, None]
    s1 = s0 * jnp.exp(g)[..., None, None]
    ks = jnp.einsum("shk,shkv->shv", k, s1, precision=_HI)
    d = (v - ks) * beta[..., None]
    s2 = s1 + k[..., :, None] * d[..., None, :]
    o = jnp.einsum("shk,shkv->shv", q, s2, precision=_HI)
    return o, pool.at[slots].set(s2)


def _chunk_terms(q, k, v, g, beta):
    """What a chunk computes before it meets the state.  Inputs chunk-major
    ``[n, H, C, .]`` (g, beta ``[n, H, C]``).  Returns (T @ v_beta,
    T @ (k_beta exp G), intra-chunk attention, q exp G, k exp(G_last - G),
    exp G_last)."""
    c = q.shape[2]
    gc = jnp.cumsum(g, axis=-1)
    i = jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
    j = jax.lax.broadcasted_iota(jnp.int32, (c, c), 1)
    diff = gc[..., :, None] - gc[..., None, :]
    decay = jnp.exp(jnp.where(i >= j, diff, -jnp.inf))      # lower incl diag
    kb = k * beta[..., None]
    vb = v * beta[..., None]
    kk = jnp.einsum("nhik,nhjk->nhij", kb, k, precision=_HI)
    t = _tri_inverse(jnp.where(i > j, kk * decay, 0.0))
    eg = jnp.exp(gc)[..., None]
    value = jnp.matmul(t, vb, precision=_HI)
    kcd = jnp.matmul(t, kb * eg, precision=_HI)
    attn = jnp.einsum("nhik,nhjk->nhij", q, k, precision=_HI) * decay
    kdec = k * jnp.exp(gc[..., -1:] - gc)[..., None]
    return value, kcd, attn, q * eg, kdec, jnp.exp(gc[..., -1])


def gdn_chunk_reference(pool, q, k, v, g, beta, tile_slot, tile_reset,
                        tile: int, chunk: int = CHUNK):
    """The tile segment.  q, k [T, H, dk]; v [T, H, dv]; g, beta [T, H];
    tile_slot [T // tile] int32; tile_reset [T // tile] bool.  Returns
    ``(o [T, H, dv], new pool)``.  Every chunk reads its slot and writes it
    back, so the carry from tile to tile goes through the pool."""
    t_rows, h, dk = q.shape
    dv = v.shape[-1]
    chunk = min(chunk, tile)
    n, per = t_rows // chunk, tile // chunk

    def cm(x):                      # [T, H, ...] -> [n, H, C, ...]
        return jnp.moveaxis(x.reshape((n, chunk) + x.shape[1:]), 1, 2)

    value, kcd, attn, qg, kdec, last = _chunk_terms(
        cm(q), cm(k), cm(v), cm(g), cm(beta))
    slots = jnp.repeat(tile_slot, per)
    # only a tile's first chunk can be a sequence's first
    reset = jnp.repeat(tile_reset, per) & (jnp.arange(n) % per == 0)

    def body(pool, xs):
        value, kcd, attn, qg, kdec, last, slot, reset = xs
        s0 = jax.lax.dynamic_index_in_dim(pool, slot, 0, keepdims=False) \
            * jnp.where(reset, 0.0, 1.0)
        v_new = value - jnp.matmul(kcd, s0, precision=_HI)
        o = jnp.matmul(qg, s0, precision=_HI) \
            + jnp.matmul(attn, v_new, precision=_HI)
        s1 = s0 * last[:, None, None] + jnp.einsum(
            "hck,hcv->hkv", kdec, v_new, precision=_HI)
        return jax.lax.dynamic_update_index_in_dim(pool, s1, slot, 0), o

    pool, o = jax.lax.scan(body, pool, (value, kcd, attn, qg, kdec, last,
                                        slots, reset))
    return jnp.moveaxis(o, 1, 2).reshape(t_rows, h, dv), pool


# --------------------------------------------------------------------- #
# Mosaic kernel (b): the decode update, one token a row
# --------------------------------------------------------------------- #
def _gdn_step_kernel(slot_ref, reset_ref, qt_ref, kt_ref, v_ref, a_ref,
                     b_ref, s_in_ref, o_ref, s_out_ref, *, hb: int):
    """Grid (rows, head groups).  All on the VPU: with ``k`` as a column
    and ``v``, ``d``, ``o`` as rows, ``S^T k`` is a sublane reduction and
    ``k d^T`` a broadcast product, so no float32 pass goes through the
    MXU.  ``o = S_new^T q = a S0^T q + (q . k) d``."""
    s = pl.program_id(0)
    keep = jnp.where(reset_ref[s] != 0, 0.0, 1.0).astype(F32)
    for j in range(hb):
        s0 = s_in_ref[0, j] * keep                        # [dk, dv]
        qc = qt_ref[0, 0, :, j:j + 1]                     # [dk, 1]
        kc = kt_ref[0, 0, :, j:j + 1]
        a = a_ref[0, j:j + 1, :]                          # [1, dv]
        ks = jnp.sum(kc * s0, axis=0, keepdims=True)      # [1, dv]
        qs = jnp.sum(qc * s0, axis=0, keepdims=True)
        qk = jnp.sum(qc * kc, axis=0, keepdims=True)      # [1, 1]
        d = b_ref[0, j:j + 1, :] * (v_ref[0, j:j + 1, :] - a * ks)
        s_out_ref[0, j] = a * s0 + kc * d
        o_ref[0, j:j + 1, :] = a * qs + qk * d


@functools.partial(jax.jit, static_argnames=("hb", "interpret"))
def _gdn_step_call(pool, q, k, v, g, beta, slots, reset, hb: int,
                   interpret: bool):
    s, h, dk = q.shape
    dv = v.shape[-1]
    hg = h // hb

    def cols(x):                    # [S, H, dk] -> [S, hg, dk, hb]
        return jnp.swapaxes(x.reshape(s, hg, hb, dk), 2, 3)

    a_row = jnp.broadcast_to(jnp.exp(g)[..., None], (s, h, dv))
    b_row = jnp.broadcast_to(beta[..., None], (s, h, dv))
    kernel = functools.partial(_gdn_step_kernel, hb=hb)
    col_spec = pl.BlockSpec((1, 1, dk, hb), lambda i, j, sl, rs: (i, j, 0, 0))
    row_spec = pl.BlockSpec((1, hb, dv), lambda i, j, sl, rs: (i, j, 0))
    pool_spec = pl.BlockSpec((1, hb, dk, dv),
                             lambda i, j, sl, rs: (sl[i], j, 0, 0))
    o, pool = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(s, hg),
            in_specs=[col_spec, col_spec, row_spec, row_spec, row_spec,
                      pool_spec],
            out_specs=[row_spec, pool_spec]),
        out_shape=[jax.ShapeDtypeStruct((s, h, dv), F32),
                   jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
        # the pool is updated in place: operand 7 (after the two scalar
        # vectors and five row operands) is output 1
        input_output_aliases={7: 1},
        interpret=interpret,
        **kernel_names(kernel),
    )(slots.astype(jnp.int32), reset.astype(jnp.int32), cols(q), cols(k),
      v, a_row, b_row, pool)
    return o, pool


# --------------------------------------------------------------------- #
# Mosaic kernel (a): the chunked rule over the tile segment
# --------------------------------------------------------------------- #
def _gdn_chunk_kernel(slot_ref, reset_ref, q_ref, k_ref, v_ref, c_ref, d_ref,
                      s_in_ref, o_ref, s_out_ref, *, hb: int, tile: int,
                      chunk: int):
    """Grid (head groups, tiles), tiles innermost: the tiles of one
    sequence follow each other and map to the same block of the pool, so
    Pallas neither fetches the slot again nor writes it back between them
    — the state is carried in the output block, read from the pool at a
    sequence's first tile and written once when the slot changes."""
    t = pl.program_id(1)
    first = jnp.logical_or(t == 0,
                           slot_ref[jnp.maximum(t - 1, 0)] != slot_ref[t])

    @pl.when(first)
    def _():
        keep = jnp.where(reset_ref[t] != 0, 0.0, 1.0).astype(F32)
        s_out_ref[...] = s_in_ref[...] * keep

    i = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    j = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    nt = (((1,), (1,)), ((), ()))          # a @ b^T
    tn = (((0,), (0,)), ((), ()))          # a^T @ b

    def head(hd, carry):
        st = s_out_ref[0, hd]                             # [dk, dv]
        for c in range(tile // chunk):
            rows = pl.ds(c * chunk, chunk)
            qc, kc, vc = q_ref[hd, rows, :], k_ref[hd, rows, :], \
                v_ref[hd, rows, :]
            cols = c_ref[hd, rows, :]                     # [C, 8]
            beta, eg, kd = cols[:, 0:1], cols[:, 1:2], cols[:, 2:3]
            decay = d_ref[hd, rows, :]                    # [C, C] lower
            kb = kc * beta
            tm = _tri_inverse(jnp.where(i > j, _dot(kb, kc, nt) * decay, 0.0))
            v_new = _dot(tm, vc * beta) - _dot(_dot(tm, kb * eg), st)
            o_ref[hd, rows, :] = _dot(qc * eg, st) \
                + _dot(_dot(qc, kc, nt) * decay, v_new)
            # exp(G_last) as a [1, dv] row (Mosaic does not broadcast a
            # [1, 1] value both ways at once): the smallest of exp(G), as
            # G falls along the chunk
            last = jnp.min(jnp.broadcast_to(eg, (chunk, st.shape[1])),
                           axis=0, keepdims=True)
            st = st * last + _dot(kc * kd, v_new, tn)
        s_out_ref[0, hd] = st
        return carry

    jax.lax.fori_loop(0, hb, head, 0)


@functools.partial(jax.jit, static_argnames=("tile", "chunk", "hb",
                                             "interpret"))
def _gdn_chunk_call(pool, q, k, v, g, beta, tile_slot, tile_reset,
                    tile: int, chunk: int, hb: int, interpret: bool):
    t_rows, h, dk = q.shape
    dv = v.shape[-1]
    n, nt = t_rows // chunk, t_rows // tile
    # per-row scalars and the decay mask are elementwise work XLA fuses;
    # the kernel gets matrices only
    gc = jnp.cumsum(g.reshape(n, chunk, h), axis=1)           # [n, C, H]
    eg = jnp.exp(gc)
    kd = jnp.exp(gc[:, -1:, :] - gc)
    cols = jnp.stack([beta.reshape(n, chunk, h), eg, kd]
                     + [jnp.zeros_like(eg)] * 5, axis=-1)     # [n, C, H, 8]
    cols = jnp.moveaxis(cols, 2, 0).reshape(h, t_rows, 8)
    gh = jnp.moveaxis(gc, 2, 0)                               # [H, n, C]
    i = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    j = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    decay = jnp.exp(jnp.where(i >= j, gh[..., :, None] - gh[..., None, :],
                              -jnp.inf)).reshape(h, t_rows, chunk)
    hm = lambda x: jnp.swapaxes(x, 0, 1)                      # head-major
    kernel = functools.partial(_gdn_chunk_kernel, hb=hb, tile=tile,
                               chunk=chunk)

    def rows(width):
        return pl.BlockSpec((hb, tile, width),
                            lambda hg, t, sl, rs: (hg, t, 0))

    pool_spec = pl.BlockSpec((1, hb, dk, dv),
                             lambda hg, t, sl, rs: (sl[t], hg, 0, 0))
    o, pool = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(h // hb, nt),
            in_specs=[rows(dk), rows(dk), rows(dv), rows(8), rows(chunk),
                      pool_spec],
            out_specs=[rows(dv), pool_spec]),
        out_shape=[jax.ShapeDtypeStruct((h, t_rows, dv), F32),
                   jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
        input_output_aliases={7: 1},
        interpret=interpret,
        **kernel_names(kernel),
    )(tile_slot.astype(jnp.int32), tile_reset.astype(jnp.int32), hm(q),
      hm(k), hm(v), cols, decay, pool)
    return hm(o), pool


# --------------------------------------------------------------------- #
# Public entries
# --------------------------------------------------------------------- #
def _head_block(h: int, want: int) -> int:
    return want if h % want == 0 else h


def _kernel_mode(interpret: Optional[bool]):
    """(run the kernel, in interpret mode): ``None`` (the served default)
    is the kernel on a TPU and the composition elsewhere."""
    if interpret is None:
        return on_tpu(), False
    return True, bool(interpret)


def gdn_step(pool, q, k, v, g, beta, slots, reset,
             interpret: Optional[bool] = None):
    """One token a row: see :func:`gdn_step_reference` for the shapes."""
    use, interp = _kernel_mode(interpret)
    if not use:
        return gdn_step_reference(pool, q, k, v, g, beta, slots, reset)
    return _gdn_step_call(pool, q, k, v, g, beta, slots, reset,
                          _head_block(q.shape[1], 8), interp)


def gdn_chunk(pool, q, k, v, g, beta, tile_slot, tile_reset, tile: int,
              interpret: Optional[bool] = None):
    """The tile segment: see :func:`gdn_chunk_reference` for the shapes."""
    use, interp = _kernel_mode(interpret)
    if not use:
        return gdn_chunk_reference(pool, q, k, v, g, beta, tile_slot,
                                   tile_reset, tile)
    return _gdn_chunk_call(pool, q, k, v, g, beta, tile_slot, tile_reset,
                           tile, min(CHUNK, tile),
                           _head_block(q.shape[1], 4), interp)


# --------------------------------------------------------------------- #
# dslint contract-checker registration (see analysis/pallas_lint.py): both
# kernels at small shapes under the checker's capture context — no kernel
# body runs.  The pool is aliased in and out and only the slots the batch
# names are visited, so the uncovered-tile rule is waived for both.
# --------------------------------------------------------------------- #
from deepspeed_tpu.analysis.registry import pallas_kernel_case  # noqa: E402


def _dslint_gdn_inputs(rows: int):
    import numpy as np

    rng = np.random.default_rng(2)
    f = lambda *shape: jnp.asarray(rng.standard_normal(shape), F32)
    return (f(5, 8, 128, 128), f(rows, 8, 128), f(rows, 8, 128),
            f(rows, 8, 128), -jnp.abs(f(rows, 8)),
            jax.nn.sigmoid(f(rows, 8)))


@pallas_kernel_case(
    "gdn_step", allow=("pallas-uncovered-tile",),
    note="gated delta rule decode update: one read and one write of each "
         "row's state slot; slots no row names keep their aliased content")
def _dslint_gdn_step():
    gdn_step(*_dslint_gdn_inputs(8), jnp.asarray([1, 0, 4, 3, 4, 4, 2, 4]),
             jnp.zeros((8,), bool), interpret=True)


@pallas_kernel_case(
    "gdn_chunk", allow=("pallas-uncovered-tile",),
    note="chunked gated delta rule over the tile segment; the state is "
         "carried in the output block across a sequence's tiles")
def _dslint_gdn_chunk():
    gdn_chunk(*_dslint_gdn_inputs(512), jnp.asarray([2, 2, 0, 4]),
              jnp.asarray([True, False, False, False]), 128, interpret=True)
