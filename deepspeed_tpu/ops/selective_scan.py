"""Selective scan — the recurrence of a Mamba (state-space) layer over a
pool of per-sequence state slots.

Per channel ``c`` of the layer's ``Di`` and state index ``n`` of its ``N``
the layer keeps one float32 value for every live sequence: a state ``s [N,
Di]`` a sequence, the channels on the lanes.  One token ``t`` with step
``dt_t [Di]`` (after the softplus), input ``x_t [Di]`` (after the
convolution), ``B_t [N]`` and ``C_t [N]`` does, with ``A [N, Di]`` negative::

    s = exp(dt_t[None, :] * A) * s + (dt_t * x_t)[None, :] * B_t[:, None]
    y_t = sum_n s[n, :] * C_t[n]

A diagonal, input-dependent decay: no matmul anywhere in the recurrence.
``D * x``, the gate ``* silu(z)``, the softplus and the inner norms are the
caller's (elementwise work XLA fuses into the projections around the scan).

The states live in a slot pool ``[slots + 1, N, Di]`` owned by the serving
engine's state manager (``inference/v2/ragged/state_pool.py``); the last
slot is scratch, where pad rows write.  Two entry points, one for each
segment of a ragged batch (``RaggedBatchWrapper.set_alignment``), as
``ops/gated_delta_rule.py`` has:

* :func:`ssm_step` — rows of one token each (a decode step, the
  single-token segment): one read and one write of each row's slot.
* :func:`ssm_chunk` — the tile segment: every ``tile`` rows belong to one
  sequence, tiles of one sequence follow each other in position order.  The
  time loop runs INSIDE the kernel over a channel block's state held in
  VMEM (registers, at the block sizes chosen): ``exp(dt A)`` is formed
  there a token at a time, so no ``[T, N, Di]`` tensor ever stands in HBM
  (the published slow path and an ``associative_scan`` both materialise
  one: 335 MB of float32 a layer for 1,024 tokens at Di 5120, N 16).

Each has a Mosaic kernel (the TPU path; ``interpret=True`` in tests) and an
XLA composition of the same mathematics (``*_reference``: the path off the
TPU and the parity oracle).  Pad rows carry ``dt = 0`` (the caller masks
them): decay ``exp(0) = 1`` and input ``0 * x = 0`` leave a state exactly
as it was; ``reset`` zeroes a slot before its first token (a sequence whose
first position is 0).  Everything is float32.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.ops.gated_delta_rule import _kernel_mode
from deepspeed_tpu.utils.platform import kernel_names

F32 = jnp.float32

#: tokens of the chunk kernel's time loop written out back to back (the
#: recurrence is a short dependency chain a channel: unrolling lets the
#: scheduler overlap one token's exp with the last one's update)
UNROLL = 8
#: channels a grid step: the decode update moves a whole slot row a step
#: where it divides (one 328 KB read and write at N 16, Di 5120: long DMAs,
#: 256 steps a layer); the chunk kernel keeps its carried state in
#: registers (N 16 x 512 lanes = 8 of the 64)
STEP_BLOCK = 5120
CHUNK_BLOCK = 512


# --------------------------------------------------------------------- #
# XLA compositions (off-TPU path, parity oracle)
# --------------------------------------------------------------------- #
def ssm_step_reference(pool, dt, dtx, b, c, a, slots, reset):
    """One token a row.  pool [P, N, Di]; dt, dtx (= dt * x) [S, Di]; b, c
    [S, N]; a [N, Di]; slots [S] int32; reset [S] bool.  Returns ``(y [S,
    Di], new pool)``."""
    s0 = pool[slots] * jnp.where(reset, 0.0, 1.0)[:, None, None]
    s1 = jnp.exp(dt[:, None, :] * a) * s0 + dtx[:, None, :] * b[:, :, None]
    y = jnp.sum(s1 * c[:, :, None], axis=1)
    return y, pool.at[slots].set(s1)


def ssm_chunk_reference(pool, dt, dtx, b, c, a, tile_slot, tile_reset,
                        tile: int):
    """The tile segment.  dt, dtx [T, Di]; b, c [T, N]; tile_slot [T //
    tile] int32; tile_reset [T // tile] bool.  Returns ``(y [T, Di], new
    pool)``.  Token after token; every tile reads its slot and writes it
    back, so the carry from tile to tile goes through the pool."""
    t_rows, di = dt.shape
    nt = t_rows // tile
    tiled = lambda x: x.reshape((nt, tile) + x.shape[1:])

    def one_tile(pool, xs):
        dt_t, dtx_t, b_t, c_t, slot, reset = xs
        s0 = jax.lax.dynamic_index_in_dim(pool, slot, 0, keepdims=False) \
            * jnp.where(reset, 0.0, 1.0)

        def token(s, row):
            dt_r, dtx_r, b_r, c_r = row
            s = jnp.exp(dt_r[None, :] * a) * s + dtx_r[None, :] * b_r[:, None]
            return s, jnp.sum(s * c_r[:, None], axis=0)

        s1, y = jax.lax.scan(token, s0, (dt_t, dtx_t, b_t, c_t))
        return jax.lax.dynamic_update_index_in_dim(pool, s1, slot, 0), y

    pool, y = jax.lax.scan(one_tile, pool, (
        tiled(dt), tiled(dtx), tiled(b), tiled(c), tile_slot, tile_reset))
    return y.reshape(t_rows, di), pool


# --------------------------------------------------------------------- #
# Mosaic kernel (b): the decode update, one token a row
# --------------------------------------------------------------------- #
def _ssm_step_kernel(slot_ref, reset_ref, dt_ref, dtx_ref, bc_ref, a_ref,
                     s_in_ref, y_ref, s_out_ref, *, rb: int):
    """Grid (channel blocks, rows), rows innermost.  All on the VPU: ``B``
    and ``C`` are columns ``[N, 1]`` broadcast along the lanes, ``dt`` and
    ``dt x`` rows broadcast along the sublanes, the read-out a sublane
    reduction.  The rows' operands arrive ``rb`` rows a block (whole
    sublane tiles of the arrays as XLA keeps them: a one-row block would
    make XLA re-tile ``dt``, ``dt x`` and ``y`` around every call), fetched
    once for ``rb`` grid steps; a step takes its own row of it."""
    s = pl.program_id(1)
    r = pl.ds(s % rb, 1)
    keep = jnp.where(reset_ref[s] != 0, 0.0, 1.0).astype(F32)
    bc = bc_ref[0]                                        # [N, 2]
    s1 = jnp.exp(dt_ref[r, :] * a_ref[...]) * (s_in_ref[0] * keep) \
        + dtx_ref[r, :] * bc[:, 0:1]
    s_out_ref[0] = s1
    y_ref[r, :] = jnp.sum(s1 * bc[:, 1:2], axis=0, keepdims=True)


@functools.partial(jax.jit, static_argnames=("cb", "interpret"))
def _ssm_step_call(pool, dt, dtx, b, c, a, slots, reset, cb: int,
                   interpret: bool):
    s, di = dt.shape
    n = a.shape[0]
    rb = 8 if s % 8 == 0 else s
    kernel = functools.partial(_ssm_step_kernel, rb=rb)
    row_spec = pl.BlockSpec((rb, cb), lambda j, i, sl, rs: (i // rb, j))
    pool_spec = pl.BlockSpec((1, n, cb), lambda j, i, sl, rs: (sl[i], 0, j))
    y, pool = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(di // cb, s),
            in_specs=[row_spec, row_spec,
                      pl.BlockSpec((1, n, 2), lambda j, i, sl, rs: (i, 0, 0)),
                      pl.BlockSpec((n, cb), lambda j, i, sl, rs: (0, j)),
                      pool_spec],
            out_specs=[row_spec, pool_spec]),
        out_shape=[jax.ShapeDtypeStruct((s, di), F32),
                   jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
        # the pool is updated in place: operand 6 (after the two scalar
        # vectors and four row operands) is output 1
        input_output_aliases={6: 1},
        interpret=interpret,
        **kernel_names(kernel),
    )(slots.astype(jnp.int32), reset.astype(jnp.int32), dt, dtx,
      jnp.stack([b, c], axis=-1), a, pool)
    return y, pool


# --------------------------------------------------------------------- #
# Mosaic kernel (a): the scan over the tile segment
# --------------------------------------------------------------------- #
def _ssm_chunk_kernel(slot_ref, reset_ref, dt_ref, dtx_ref, bc_ref, a_ref,
                      s_in_ref, y_ref, s_out_ref, *, tile: int, unroll: int):
    """Grid (channel blocks, tiles), tiles innermost: the tiles of one
    sequence follow each other and map to the same block of the pool, so
    Pallas neither fetches the slot again nor writes it back between them
    — the state is carried in the output block, read from the pool at a
    sequence's first tile and written once when the slot changes.  Inside a
    tile the state is a loop-carried value ``[N, cb]`` (8 vector registers
    at N 16, cb 512) and one token is: two row loads, one column pair, an
    ``exp``, two multiply-adds and a sublane reduction.  ``tile`` is a
    multiple of ``unroll``."""
    t = pl.program_id(1)
    first = jnp.logical_or(t == 0,
                           slot_ref[jnp.maximum(t - 1, 0)] != slot_ref[t])

    @pl.when(first)
    def _():
        keep = jnp.where(reset_ref[t] != 0, 0.0, 1.0).astype(F32)
        s_out_ref[...] = s_in_ref[...] * keep

    a = a_ref[...]                                        # [N, cb]

    def group(g, s):
        # ``unroll`` tokens written out: their rows arrive as one aligned
        # load and their read-outs leave as one aligned store
        rows = pl.ds(pl.multiple_of(g * unroll, unroll), unroll)
        dt, dtx, bc = dt_ref[rows, :], dtx_ref[rows, :], bc_ref[rows]
        ys = []
        for k in range(unroll):
            s = jnp.exp(dt[k:k + 1, :] * a) * s \
                + dtx[k:k + 1, :] * bc[k, :, 0:1]
            ys.append(jnp.sum(s * bc[k, :, 1:2], axis=0, keepdims=True))
        y_ref[rows, :] = jnp.concatenate(ys, axis=0)
        return s

    s_out_ref[0] = jax.lax.fori_loop(0, tile // unroll, group, s_out_ref[0])


@functools.partial(jax.jit, static_argnames=("tile", "cb", "interpret"))
def _ssm_chunk_call(pool, dt, dtx, b, c, a, tile_slot, tile_reset,
                    tile: int, cb: int, interpret: bool):
    t_rows, di = dt.shape
    n = a.shape[0]
    kernel = functools.partial(
        _ssm_chunk_kernel, tile=tile,
        unroll=UNROLL if tile % UNROLL == 0 else 1)
    row_spec = pl.BlockSpec((tile, cb), lambda j, t, sl, rs: (t, j))
    pool_spec = pl.BlockSpec((1, n, cb), lambda j, t, sl, rs: (sl[t], 0, j))
    y, pool = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(di // cb, t_rows // tile),
            in_specs=[row_spec, row_spec,
                      pl.BlockSpec((tile, n, 2),
                                   lambda j, t, sl, rs: (t, 0, 0)),
                      pl.BlockSpec((n, cb), lambda j, t, sl, rs: (0, j)),
                      pool_spec],
            out_specs=[row_spec, pool_spec]),
        out_shape=[jax.ShapeDtypeStruct((t_rows, di), F32),
                   jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
        input_output_aliases={6: 1},
        interpret=interpret,
        **kernel_names(kernel),
    )(tile_slot.astype(jnp.int32), tile_reset.astype(jnp.int32), dt, dtx,
      jnp.stack([b, c], axis=-1), a, pool)
    return y, pool


# --------------------------------------------------------------------- #
# Public entries
# --------------------------------------------------------------------- #
def _channel_block(di: int, want: int) -> int:
    """The widest block of whole lane tiles, at most ``want``, that divides
    the channels; all of them where none does (interpret mode's sizes)."""
    for cb in range(want, 0, -128):
        if di % cb == 0:
            return cb
    return di


def ssm_step(pool, dt, dtx, b, c, a, slots, reset,
             interpret: Optional[bool] = None):
    """One token a row: see :func:`ssm_step_reference` for the shapes."""
    use, interp = _kernel_mode(interpret)
    if not use:
        return ssm_step_reference(pool, dt, dtx, b, c, a, slots, reset)
    return _ssm_step_call(pool, dt, dtx, b, c, a, slots, reset,
                          _channel_block(dt.shape[1], STEP_BLOCK), interp)


def ssm_chunk(pool, dt, dtx, b, c, a, tile_slot, tile_reset, tile: int,
              interpret: Optional[bool] = None):
    """The tile segment: see :func:`ssm_chunk_reference` for the shapes."""
    use, interp = _kernel_mode(interpret)
    if not use:
        return ssm_chunk_reference(pool, dt, dtx, b, c, a, tile_slot,
                                   tile_reset, tile)
    return _ssm_chunk_call(pool, dt, dtx, b, c, a, tile_slot, tile_reset,
                           tile, _channel_block(dt.shape[1], CHUNK_BLOCK),
                           interp)


# --------------------------------------------------------------------- #
# dslint contract-checker registration (see analysis/pallas_lint.py): both
# kernels at small shapes under the checker's capture context — no kernel
# body runs.  The pool is aliased in and out and only the slots the batch
# names are visited, so the uncovered-tile rule is waived for both.
# --------------------------------------------------------------------- #
from deepspeed_tpu.analysis.registry import pallas_kernel_case  # noqa: E402


def _dslint_ssm_inputs(rows: int, n: int = 16, di: int = 512):
    import numpy as np

    rng = np.random.default_rng(3)
    f = lambda *shape: jnp.asarray(rng.standard_normal(shape), F32)
    dt = jax.nn.softplus(f(rows, di) - 4.0)
    return (f(5, n, di), dt, dt * f(rows, di), f(rows, n), f(rows, n),
            -jnp.exp(f(n, di)))


@pallas_kernel_case(
    "ssm_step", allow=("pallas-uncovered-tile",),
    note="selective-scan decode update: one read and one write of each "
         "row's state slot; slots no row names keep their aliased content")
def _dslint_ssm_step():
    ssm_step(*_dslint_ssm_inputs(8), jnp.asarray([1, 0, 4, 3, 4, 4, 2, 4]),
             jnp.zeros((8,), bool), interpret=True)


@pallas_kernel_case(
    "ssm_chunk", allow=("pallas-uncovered-tile",),
    note="selective scan over the tile segment, the time loop inside the "
         "kernel; the state is carried in the output block across a "
         "sequence's tiles")
def _dslint_ssm_chunk():
    ssm_chunk(*_dslint_ssm_inputs(512), jnp.asarray([2, 2, 0, 4]),
              jnp.asarray([True, False, False, False]), 128, interpret=True)
