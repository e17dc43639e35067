"""State-space duality (Mamba-2) — the recurrence of a Mamba-2 layer over a
pool of per-sequence state slots.

A layer has ``H`` heads of ``P`` channels (``Di = H P``) and ``N`` states.
Per sequence it keeps one float32 value for every (state, channel): ``s [N,
Di]``, the channels on the lanes, head ``h`` in lanes ``[h P, (h + 1) P)``.
One token ``t`` with a step ``dt_t [H]`` (after the softplus), input ``x_t
[Di]`` (after the convolution) and ``B_t``, ``C_t [N]`` SHARED by all heads
(one group) does, with ONE negative ``A_h`` a head::

    s[:, h] = exp(dt_t[h] A_h) * s[:, h] + B_t[:, None] * (dt_t[h] x_t[h])[None, :]
    y_t = sum_n C_t[n] * s[n, :]

What differs from the selective scan of ``ops/selective_scan.py`` (Mamba-1)
is what makes the matmul form possible: the decay is a scalar a head and
token, not a value a (state, channel), so inside a chunk of ``L`` tokens::

    cum_t = sum_{u <= t} dt_u A                          (a head; <= 0)
    y_t   = exp(cum_t) (C_t s_in) + sum_{u <= t} (C_t . B_u) exp(cum_t - cum_u) dt_u x_u
    s_out = exp(cum_L) s_in + sum_u B_u^T exp(cum_L - cum_u) dt_u x_u

``C B^T`` is one ``[L, L]`` product for all heads, ``C s_in`` and the
state's update are ``[L, N] x [N, Di]`` and ``[N, L] x [L, Di]`` products
over every channel at once, and a head's own part is its ``[L, L]`` decay
mask times ``C B^T`` against its ``P`` columns of ``dt x``: all on the MXU.
Decays are ``exp`` of DIFFERENCES of the cumulative ``dt A`` (each <= 0),
never ratios of cumulative products.  ``D x``, the gate, the gated norm,
the softplus and ``dt x`` are the caller's.

The states live in a slot pool ``[slots + 1, N, Di]`` owned by the serving
engine's state manager (``inference/v2/ragged/state_pool.py``); the last
slot is scratch, where pad rows write.  Two entry points, one for each
segment of a ragged batch, as ``ops/selective_scan.py`` has:

* :func:`ssd_step` — rows of one token each: one read and one write of each
  row's slot.  The decay arrives as a row ``[Di]`` a token (``dt_t[h] A_h``
  repeated over the head's lanes by the wrapper), so no ``[N, Di]`` copy of
  ``A`` stands in HBM and the ``exp`` is taken of ``Di`` values a row, not
  ``N Di``.
* :func:`ssd_chunk` — the tile segment: every ``tile`` rows belong to one
  sequence, tiles of one sequence follow each other in position order; a
  tile is one chunk of the form above and the state is carried from tile
  to tile in the pool's output block.

Each has a Mosaic kernel (the TPU path; ``interpret=True`` in tests) and an
XLA composition of the same mathematics, token after token
(``*_reference``: the path off the TPU and the parity oracle).  Pad rows
carry ``dt = 0`` (the caller masks them): decay ``exp(0) = 1`` and input
``0`` leave a state exactly as it was; ``reset`` zeroes a slot before its
first token (a sequence whose first position is 0).  Everything is
float32, every product at float32 passes.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.ops.gated_delta_rule import _kernel_mode, _mm
from deepspeed_tpu.utils.platform import kernel_names

F32 = jnp.float32
LANES = 128

#: channels a grid step.  The decode update moves ``[N, STEP_BLOCK]`` of a
#: slot in and out a step (1 MB at N 128: four buffers of it in VMEM); the
#: chunk kernel holds that much of the state and ``tile`` rows of ``dt x``,
#: the cumulative decay and ``y`` beside it.
STEP_BLOCK = 2048
CHUNK_BLOCK = 1024


def head_lanes(per_head, di: int):
    """``[rows, H]`` -> ``[rows, Di]``: a head's value on each of its
    lanes."""
    return jnp.repeat(per_head, di // per_head.shape[-1], axis=-1)


# --------------------------------------------------------------------- #
# XLA compositions (off-TPU path, parity oracle)
# --------------------------------------------------------------------- #
def ssd_step_reference(pool, da, dtx, b, c, slots, reset):
    """One token a row.  pool [P, N, Di]; da (= dt A, <= 0) [S, H]; dtx (=
    dt x) [S, Di]; b, c [S, N]; slots [S] int32; reset [S] bool.  Returns
    ``(y [S, Di], new pool)``."""
    s0 = pool[slots] * jnp.where(reset, 0.0, 1.0)[:, None, None]
    decay = jnp.exp(head_lanes(da, dtx.shape[1]))
    s1 = decay[:, None, :] * s0 + dtx[:, None, :] * b[:, :, None]
    y = jnp.sum(s1 * c[:, :, None], axis=1)
    return y, pool.at[slots].set(s1)


def ssd_chunk_reference(pool, da, dtx, b, c, tile_slot, tile_reset,
                        tile: int):
    """The tile segment.  da [T, H]; dtx [T, Di]; b, c [T, N]; tile_slot [T
    // tile] int32; tile_reset [T // tile] bool.  Returns ``(y [T, Di], new
    pool)``.  Token after token; every tile reads its slot and writes it
    back, so the carry from tile to tile goes through the pool."""
    t_rows, di = dtx.shape
    nt = t_rows // tile
    tiled = lambda x: x.reshape((nt, tile) + x.shape[1:])
    decay = jnp.exp(head_lanes(da, di))

    def one_tile(pool, xs):
        decay_t, dtx_t, b_t, c_t, slot, reset = xs
        s0 = jax.lax.dynamic_index_in_dim(pool, slot, 0, keepdims=False) \
            * jnp.where(reset, 0.0, 1.0)

        def token(s, row):
            decay_r, dtx_r, b_r, c_r = row
            s = decay_r[None, :] * s + dtx_r[None, :] * b_r[:, None]
            return s, jnp.sum(s * c_r[:, None], axis=0)

        s1, y = jax.lax.scan(token, s0, (decay_t, dtx_t, b_t, c_t))
        return jax.lax.dynamic_update_index_in_dim(pool, s1, slot, 0), y

    pool, y = jax.lax.scan(one_tile, pool, (
        tiled(decay), tiled(dtx), tiled(b), tiled(c), tile_slot, tile_reset))
    return y.reshape(t_rows, di), pool


# --------------------------------------------------------------------- #
# Mosaic kernel (b): the decode update, one token a row
# --------------------------------------------------------------------- #
def _ssd_step_kernel(slot_ref, reset_ref, da_ref, dtx_ref, bc_ref, s_in_ref,
                     y_ref, s_out_ref, *, rb: int):
    """Grid (channel blocks, rows), rows innermost.  All on the VPU: ``B``
    and ``C`` are columns ``[N, 1]`` broadcast along the lanes, the decay
    and ``dt x`` rows broadcast along the sublanes, the read-out a sublane
    reduction.  The rows' operands arrive ``rb`` rows a block, fetched once
    for ``rb`` grid steps; a step takes its own row of it (as
    ``_ssm_step_kernel``, whose ``[N, Di]`` operand ``A`` is gone: the
    decay is one row)."""
    s = pl.program_id(1)
    r = pl.ds(s % rb, 1)
    keep = jnp.where(reset_ref[s] != 0, 0.0, 1.0).astype(F32)
    bc = bc_ref[0]                                        # [N, 2]
    s1 = jnp.exp(da_ref[r, :]) * (s_in_ref[0] * keep) \
        + dtx_ref[r, :] * bc[:, 0:1]
    s_out_ref[0] = s1
    y_ref[r, :] = jnp.sum(s1 * bc[:, 1:2], axis=0, keepdims=True)


@functools.partial(jax.jit, static_argnames=("cb", "interpret"))
def _ssd_step_call(pool, da, dtx, b, c, slots, reset, cb: int,
                   interpret: bool):
    s, di = dtx.shape
    n = pool.shape[1]
    rb = 8 if s % 8 == 0 else s
    kernel = functools.partial(_ssd_step_kernel, rb=rb)
    row_spec = pl.BlockSpec((rb, cb), lambda j, i, sl, rs: (i // rb, j))
    pool_spec = pl.BlockSpec((1, n, cb), lambda j, i, sl, rs: (sl[i], 0, j))
    y, pool = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(di // cb, s),
            in_specs=[row_spec, row_spec,
                      pl.BlockSpec((1, n, 2), lambda j, i, sl, rs: (i, 0, 0)),
                      pool_spec],
            out_specs=[row_spec, pool_spec]),
        out_shape=[jax.ShapeDtypeStruct((s, di), F32),
                   jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
        # the pool is updated in place: operand 5 (after the two scalar
        # vectors and three row operands) is output 1
        input_output_aliases={5: 1},
        interpret=interpret,
        **kernel_names(kernel),
    )(slots.astype(jnp.int32), reset.astype(jnp.int32), head_lanes(da, di),
      dtx, jnp.stack([b, c], axis=-1), pool)
    return y, pool


# --------------------------------------------------------------------- #
# Mosaic kernel (a): the chunked form over the tile segment
# --------------------------------------------------------------------- #
def _ssd_chunk_kernel(slot_ref, reset_ref, cum_ref, cumt_ref, dtx_ref, b_ref,
                      bt_ref, c_ref, s_in_ref, y_ref, s_out_ref, *,
                      tile: int, p: int):
    """Grid (channel blocks, tiles), tiles innermost: the tiles of one
    sequence follow each other and map to the same block of the pool, so
    Pallas neither fetches the slot again nor writes it back between them
    — the state is carried in the output block, read from the pool at a
    sequence's first tile and written once when the slot changes.

    A tile is one chunk.  ``cum [tile, cb]`` is the cumulative ``dt A``
    inside the tile on every channel's lane, ``cumt [heads, tile]`` the
    same a head with the tokens on the lanes (a head's decay mask needs
    both: ``exp(cum_t - cum_u)``, ``t`` down the sublanes and ``u`` along
    the lanes).  ``C B^T``, ``C s`` and ``B^T (.)`` run once over the
    block's channels; the masked ``[tile, tile]`` product runs a head.  A
    head of fewer than 128 lanes shares its lane tile: the group's ``dt x``
    with the other heads' lanes zeroed is the right operand, so each head's
    product lands on its own lanes and nothing is shifted."""
    t = pl.program_id(1)
    first = jnp.logical_or(t == 0,
                           slot_ref[jnp.maximum(t - 1, 0)] != slot_ref[t])

    @pl.when(first)
    def _():
        keep = jnp.where(reset_ref[t] != 0, 0.0, 1.0).astype(F32)
        s_out_ref[...] = s_in_ref[...] * keep

    s0 = s_out_ref[0]                                     # [N, cb]
    cum, dtx, c, bt = cum_ref[...], dtx_ref[...], c_ref[...], bt_ref[...]
    cb = dtx.shape[1]
    row = jax.lax.broadcasted_iota(jnp.int32, (tile, tile), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (tile, tile), 1)
    causal = row >= col
    cbt = _mm(c, bt)                                      # C B^T [L, L]
    y_in = _mm(c, s0) * jnp.exp(cum)                      # the state's part
    # lanes a group of heads shares: one head where it fills whole tiles
    gw = p if p >= LANES else min(LANES, cb)
    lane = jax.lax.broadcasted_iota(jnp.int32, (tile, gw), 1)
    for g in range(cb // gw):
        lanes = slice(g * gw, (g + 1) * gw)
        xg, acc = dtx[:, lanes], y_in[:, lanes]
        for q in range(gw // p):
            h = g * (gw // p) + q
            # exp of a difference of cumulative sums, <= 0 where it is kept
            diff = cum[:, h * p:h * p + 1] - cumt_ref[0, h:h + 1, :]
            w = cbt * jnp.exp(jnp.where(causal, diff, -1e30))
            xq = xg if gw == p else jnp.where(
                (lane >= q * p) & (lane < (q + 1) * p), xg, 0.0)
            acc = acc + _mm(w, xq)
        y_ref[:, lanes] = acc
    last = cum[tile - 1:tile, :]
    s_out_ref[0] = jnp.exp(last) * s0 + _mm(bt, jnp.exp(last - cum) * dtx)


@functools.partial(jax.jit, static_argnames=("tile", "cb", "interpret"))
def _ssd_chunk_call(pool, da, dtx, b, c, tile_slot, tile_reset, tile: int,
                    cb: int, interpret: bool):
    t_rows, di = dtx.shape
    h, n = da.shape[1], pool.shape[1]
    p, nt, nb = di // h, t_rows // tile, di // cb
    hb = cb // p
    # the cumulative dt A inside each tile, a head: [T, H]
    cum = jnp.cumsum(da.reshape(nt, tile, h), axis=1).reshape(t_rows, h)
    cumt = cum.reshape(t_rows, nb, hb).transpose(1, 2, 0)   # [nb, hb, T]
    kernel = functools.partial(_ssd_chunk_kernel, tile=tile, p=p)
    row_spec = pl.BlockSpec((tile, cb), lambda j, t, sl, rs: (t, j))
    bc_spec = pl.BlockSpec((tile, n), lambda j, t, sl, rs: (t, 0))
    pool_spec = pl.BlockSpec((1, n, cb), lambda j, t, sl, rs: (sl[t], 0, j))
    y, pool = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(nb, nt),
            in_specs=[row_spec,
                      pl.BlockSpec((1, hb, tile),
                                   lambda j, t, sl, rs: (j, 0, t)),
                      row_spec, bc_spec,
                      pl.BlockSpec((n, tile), lambda j, t, sl, rs: (0, t)),
                      bc_spec, pool_spec],
            out_specs=[row_spec, pool_spec]),
        out_shape=[jax.ShapeDtypeStruct((t_rows, di), F32),
                   jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
        # the pool is updated in place: operand 8 (after the two scalar
        # vectors and six row operands) is output 1
        input_output_aliases={8: 1},
        interpret=interpret,
        **kernel_names(kernel),
    )(tile_slot.astype(jnp.int32), tile_reset.astype(jnp.int32),
      head_lanes(cum, di), cumt, dtx, b, b.T, c, pool)
    return y, pool


# --------------------------------------------------------------------- #
# Public entries
# --------------------------------------------------------------------- #
def _channel_block(di: int, p: int, want: int) -> int:
    """The widest block of whole lane tiles and whole heads, at most
    ``want``, that divides the channels; all of them where none does
    (interpret mode's sizes)."""
    for cb in range(want, 0, -LANES):
        if di % cb == 0 and cb % p == 0:
            return cb
    return di


def _head_width(pool, da, dtx) -> int:
    """``P``, checked against what the kernels' lane groups can hold."""
    di, h = dtx.shape[1], da.shape[1]
    p = di // max(h, 1)
    whole = p % LANES == 0              # a head is whole lane tiles
    shared = LANES % p == 0 and (di <= LANES or di % LANES == 0)
    if pool.shape[2] != di or p * h != di or not (whole or shared):
        raise ValueError(
            f"ssd: a pool {pool.shape} with {h} heads over {di} channels: "
            f"the state is [slots, N, H P] and a head's P channels divide "
            f"a lane tile or are whole tiles")
    return p


def ssd_step(pool, da, dtx, b, c, slots, reset,
             interpret: Optional[bool] = None):
    """One token a row: see :func:`ssd_step_reference` for the shapes."""
    use, interp = _kernel_mode(interpret)
    if not use:
        return ssd_step_reference(pool, da, dtx, b, c, slots, reset)
    cb = _channel_block(dtx.shape[1], _head_width(pool, da, dtx), STEP_BLOCK)
    return _ssd_step_call(pool, da, dtx, b, c, slots, reset, cb, interp)


def ssd_chunk(pool, da, dtx, b, c, tile_slot, tile_reset, tile: int,
              interpret: Optional[bool] = None):
    """The tile segment: see :func:`ssd_chunk_reference` for the shapes."""
    use, interp = _kernel_mode(interpret)
    if not use:
        return ssd_chunk_reference(pool, da, dtx, b, c, tile_slot,
                                   tile_reset, tile)
    cb = _channel_block(dtx.shape[1], _head_width(pool, da, dtx),
                        CHUNK_BLOCK)
    return _ssd_chunk_call(pool, da, dtx, b, c, tile_slot, tile_reset, tile,
                           cb, interp)


# --------------------------------------------------------------------- #
# dslint contract-checker registration (see analysis/pallas_lint.py): both
# kernels at small shapes under the checker's capture context — no kernel
# body runs.  The pool is aliased in and out and only the slots the batch
# names are visited, so the uncovered-tile rule is waived for both.
# --------------------------------------------------------------------- #
from deepspeed_tpu.analysis.registry import pallas_kernel_case  # noqa: E402


def _dslint_ssd_inputs(rows: int, n: int = 128, h: int = 4, p: int = 64):
    import numpy as np

    rng = np.random.default_rng(4)
    f = lambda *shape: jnp.asarray(rng.standard_normal(shape), F32)
    dt = jax.nn.softplus(f(rows, h) - 4.0)
    return (f(5, n, h * p), -dt * jnp.exp(f(h)),
            head_lanes(dt, h * p) * f(rows, h * p), f(rows, n), f(rows, n))


@pallas_kernel_case(
    "ssd_step", allow=("pallas-uncovered-tile",),
    note="Mamba-2 decode update: one read and one write of each row's "
         "state slot; slots no row names keep their aliased content")
def _dslint_ssd_step():
    ssd_step(*_dslint_ssd_inputs(8), jnp.asarray([1, 0, 4, 3, 4, 4, 2, 4]),
             jnp.zeros((8,), bool), interpret=True)


@pallas_kernel_case(
    "ssd_chunk", allow=("pallas-uncovered-tile",),
    note="Mamba-2 chunked form over the tile segment, a tile a chunk; the "
         "state is carried in the output block across a sequence's tiles")
def _dslint_ssd_chunk():
    ssd_chunk(*_dslint_ssd_inputs(512), jnp.asarray([2, 2, 0, 4]),
              jnp.asarray([True, False, False, False]), 128, interpret=True)
