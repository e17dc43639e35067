"""Grouped (ragged) expert GEMM — the Megablocks-style kernel family.

Reference analog: ``inference/v2/kernels/cutlass_ops/moe_gemm/`` (grouped
expert GEMM over tokens sorted by expert) + ``ragged_ops/moe_scatter`` /
``moe_gather`` (the sort/unsort around it).  The repo's previous MoE path
computed EVERY expert over EVERY token and masked — E/k× redundant FLOPs
(8×/2 for Mixtral).

``gmm(lhs, rhs, group_sizes)`` multiplies contiguous row-groups of
``lhs [M, K]`` against per-group weight matrices ``rhs [E, K, N]``:

    out[start_e:end_e] = lhs[start_e:end_e] @ rhs[e]

with ``start/end`` the running offsets of ``group_sizes`` (dynamic,
data-dependent — token routing decides them at run time).

TPU design: group boundaries are dynamic but the GRID must be static, so
the kernel enumerates a fixed worst-case list of work units — one per
(m-tile, group) pair that can overlap, ``num_tiles + E - 1`` of them
(each extra group adds at most one shared boundary tile).  The metadata
(work→group, work→m-tile, group start/end rows) is computed in XLA from
``group_sizes`` and scalar-prefetched into SMEM, where it DRIVES THE
BLOCK-SPEC INDEX MAPS: each work unit DMAs exactly the lhs m-tile and the
rhs slice of ITS group (the forward copies that slice itself: THE WEIGHT
RING, below).  Rows of a shared boundary tile are masked by the
group's row range, so every output row is written by exactly one work
unit.  The list is as long as the worst case; the units past the ones a
call needs (``num_work``: most of the list when the matrices are a share
of a wider router's experts and the groups sum to a fraction of ``M``)
name the last live unit's blocks with an empty row range and are SKIPPED:
the kernels test ``row_end > row_start`` and run no MXU pass and no store
for them, so such a unit costs a grid step and nothing else (no DMA
either: its block indices did not change).  The same metadata drives the
two backward kernels (dlhs accumulates over n-tiles; drhs is the "tgmm" —
per-group lhsᵀ@dout accumulated over the group's work units), wired as a
``custom_vjp`` so dropless MoE TRAINING differentiates through the kernel.

THE WEIGHT RING (forward only).  A call's time is its experts' weights
streaming in once, if nothing stops the stream.  ``pallas_call``'s grid
pipeline fetches the blocks of step ``i + 1`` while step ``i`` runs, ONE
step ahead: a step whose successor is the same expert (the expert's rows
cross a row tile) moves a row tile under its pass, the stream idles, and
the next expert's block is then waited for whole: ``sum max(pass, next
DMA)``, a third over the stream at 1.5 units an expert.  So the forward
leaves ``rhs`` in HBM and keeps a ring of ``slots`` ``[K, tile_n]`` VMEM
blocks it fills itself (``_gmm_kernel``): at the first unit of a block it
starts the block ``slots - 1`` places ahead in the call's walk (the live
groups in rising order, n-tile after n-tile: ``make_block_metadata``) into
the slot the block before has just left, then waits for its own; every
other unit only multiplies.  The next block is in flight under EVERY step
of the current one, across n-tiles too: ``max(sum DMA, sum pass)``.  Where
an expert is one unit (a decode tick) the ring is the double buffer the
pipeline was.  lhs and out stay on the grid pipeline; the backward kernels
are as they were.

TILES.  Every work unit is a full ``[tile_m, K] x [K, tile_n]`` MXU pass,
however few of its rows are the group's: a call multiplies ``m + (E - 1)
* tile_m`` rows at worst, ``m / E + tile_m`` an expert, beside the
expert's weights that stream in once.  A bf16 weight block has to
multiply ``_MXU_BOUND_ROWS`` (~240 on a v5e: FLOP/s over bytes/s) rows
before its passes take as long as its DMA, so a DECODE tick's rows (a few
an expert) want the SMALLEST row tile: at 512 rows the padding alone makes
the MXU, not the weight stream, set the call's time.  The forward and the
backward budget VMEM for different things and get their tiles from
different rules: the forward (``_pick_tiles``) holds double-buffered lhs
and out blocks and the weight ring and nothing else (its one dot covers
the whole K), so it affords the widest ``tile_n``; the two backward
kernels also hold a
float32 accumulator (``(tile_m, K)`` for dlhs, ``(K, tile_n)`` for drhs)
and re-read weights per work unit (dlhs), so ``gmm``'s VJP picks theirs
itself (``_pick_backward_tiles``) from the shapes it is handed.

The forward accumulates in the MXU's float32 over the whole K in one dot;
the backward kernels accumulate in fp32 VMEM scratch regardless of input
dtype.
"""

from __future__ import annotations

import functools
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.utils.platform import kernel_names, on_tpu


# --------------------------------------------------------------------- #
# Work-unit metadata (XLA, cheap): static-length enumeration of
# (group, m-tile) pairs covering all group rows.
# --------------------------------------------------------------------- #
def make_group_metadata(group_sizes: jnp.ndarray, m: int, tile_m: int):
    """group_sizes: [E] int32 summing to <= m.  Returns
    (group_ids [W], m_tile_ids [W], group_starts [E], group_ends [E],
    num_work []) with W = m // tile_m + E - 1 static."""
    e = group_sizes.shape[0]
    m_tiles = m // tile_m
    w = m_tiles + e - 1
    ends = jnp.cumsum(group_sizes)
    starts = ends - group_sizes
    # tiles touched by each group (empty groups touch none)
    first = starts // tile_m
    last = jnp.where(group_sizes > 0, (ends - 1) // tile_m, first - 1)
    ntiles = jnp.maximum(last - first + 1, 0)
    work_end = jnp.cumsum(ntiles)
    work_start = work_end - ntiles
    idx = jnp.arange(w, dtype=jnp.int32)
    num_work = work_end[-1]
    # invalid (>= num_work) units DUPLICATE the last valid unit (same
    # group, same m-tile — so they never trigger an init/flush boundary
    # in any kernel, and the pipeline moves no block for them) but get an
    # EMPTY row range, which every kernel reads as "skip": no dot, no
    # store
    idx_c = jnp.minimum(idx, jnp.maximum(num_work - 1, 0))
    group_ids = jnp.searchsorted(work_end, idx_c, side="right").astype(
        jnp.int32)
    group_ids = jnp.minimum(group_ids, e - 1)
    m_tile_ids = (first[group_ids] + (idx_c - work_start[group_ids])
                  ).astype(jnp.int32)
    valid = idx < num_work
    w_row_start = jnp.where(valid, starts[group_ids], 0).astype(jnp.int32)
    w_row_end = jnp.where(valid, ends[group_ids], 0).astype(jnp.int32)
    return group_ids, m_tile_ids, w_row_start, w_row_end, num_work


def make_block_metadata(group_sizes: jnp.ndarray):
    """What the forward's weight ring walks.  A BLOCK is one group's
    ``[K, tile_n]`` slice of ``rhs``; within an n-tile the live units visit
    the groups that hold rows in rising order, each once, and every n-tile
    repeats that walk, so three small arrays name every block of a call:
    ``block_of [E]`` the place of a group's block in the walk, ``next_live
    [E]`` the group of the block after it (after the last one: the first
    again, which is how the kernel knows that the walk wrapped into the
    next n-tile: ``next_live[g] <= g``), ``num_blocks [1]``."""
    e = group_sizes.shape[0]
    held = (group_sizes > 0).astype(jnp.int32)
    seen = jnp.cumsum(held)                  # live groups up to and with g
    # the b-th live group: as many groups as have seen <= b stand before it
    block_groups = jnp.sum(
        seen[None, :] <= jnp.arange(e, dtype=jnp.int32)[:, None], axis=1)
    num_blocks = seen[-1]
    after = jnp.where(seen < num_blocks, seen, 0)    # walk place of the next
    next_live = jnp.minimum(block_groups[after], e - 1).astype(jnp.int32)
    return ((seen - held).astype(jnp.int32), next_live,
            num_blocks.astype(jnp.int32)[None])


# --------------------------------------------------------------------- #
# Forward kernel: out[M, N]
# --------------------------------------------------------------------- #
def _gmm_kernel(group_ids, m_tile_ids, row_start, row_end, block_of,
                next_live, num_blocks, lhs_ref, rhs_hbm, out_ref, ring, sems,
                *, tile_m: int, tile_n: int, n_tiles: int):
    j = pl.program_id(0)
    w = pl.program_id(1)
    mt = m_tile_ids[w]
    slots = ring.shape[0]

    def copy(g_, j_, slot_):
        src = rhs_hbm.at[g_] if n_tiles == 1 else rhs_hbm.at[
            g_, :, pl.ds(pl.multiple_of(j_ * tile_n, 128), tile_n)]
        return pltpu.make_async_copy(src, ring.at[slot_], sems.at[slot_])

    # first work unit visiting this m-tile initialises the output block
    @pl.when(jnp.logical_or(w == 0, m_tile_ids[w - 1] != mt))
    def _():
        out_ref[:] = jnp.zeros_like(out_ref)

    # a unit past ``num_work`` holds no rows: no MXU pass, no store, and it
    # starts and waits for no block
    @pl.when(row_end[w] > row_start[w])
    def _():
        g = group_ids[w]
        # this unit's block among all the call walks, n-tile after n-tile,
        # and the ring slot that holds it
        place = j * num_blocks[0] + block_of[g]
        slot = jax.lax.rem(place, slots)

        def start_ahead(hops: int):
            """Start the block ``hops`` places after this one, if the call
            holds one there."""
            g_, j_ = g, j
            for _ in range(hops):
                nxt = next_live[g_]
                j_ = j_ + (nxt <= g_).astype(jnp.int32)  # wrapped: next j
                g_ = nxt

            @pl.when(j_ < n_tiles)
            def _():
                copy(g_, j_, jax.lax.rem(place + hops, slots)).start()

        # THE WEIGHT RING (module docstring): at the FIRST unit of a block
        # the block ``slots - 1`` places ahead is started into the slot the
        # block before this one has just left (the grid is sequential, both
        # axes "arbitrary": its last reader is done), then this block is
        # waited for; every other unit only multiplies.
        @pl.when(jnp.logical_or(
            w == 0, group_ids[jnp.maximum(w - 1, 0)] != g))
        def _():
            @pl.when(jnp.logical_and(j == 0, w == 0))
            def _():
                for hops in range(slots - 1):    # the call's first blocks
                    start_ahead(hops)
            start_ahead(slots - 1)
            copy(0, 0, slot).wait()    # a wait reads the shape alone

        rows = mt * tile_m + jax.lax.broadcasted_iota(
            jnp.int32, (tile_m, 1), 0)
        keep = (rows >= row_start[w]) & (rows < row_end[w])
        partial = jax.lax.dot_general(
            lhs_ref[:], ring[slot], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        out_ref[:] = jnp.where(keep, partial.astype(out_ref.dtype),
                               out_ref[:])


@functools.partial(jax.jit, static_argnames=("tile_m", "tile_n",
                                             "interpret", "slots"))
def _gmm_fwd_kernel_call(lhs, rhs, group_sizes, tile_m: int, tile_n: int,
                         interpret: bool, slots: Optional[int] = None):
    m, k = lhs.shape
    e, _, n = rhs.shape
    gids, mtids, rs, re_, _ = make_group_metadata(group_sizes, m, tile_m)
    w = gids.shape[0]
    size = lhs.dtype.itemsize
    if slots is None:
        slots = _ring_slots(tile_m, k, tile_n, size)
    # n-major grid: within one n-tile the work units of a group are
    # consecutive, so each group's rhs slice is DMAed ONCE per n-tile
    # (total rhs traffic = E*K*N); the lhs m-tiles are re-read per
    # n-tile, which wide tile_n keeps small.  The opposite (work-major)
    # order re-reads each group's FULL rhs per work unit — W*K*N bytes,
    # an order of magnitude worse at training token counts.
    grid = (n // tile_n, w)
    kernel = functools.partial(_gmm_kernel, tile_m=tile_m, tile_n=tile_n,
                               n_tiles=n // tile_n)
    # tiles over the default budget (``_pick_tiles`` hands them out for one
    # kind of N) bring the scoped limit they need
    need = _forward_vmem(tile_m, k, tile_n, size, slots)
    limit = {"vmem_limit_bytes": need + _VMEM_HEADROOM} \
        if need > _VMEM_BUDGET else {}
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=7,
            grid=grid,
            in_specs=[
                pl.BlockSpec((tile_m, k), lambda j, w, g, mt, *_: (mt[w], 0)),
                # the weights stay in HBM: the kernel's ring reads them
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec(
                (tile_m, tile_n), lambda j, w, g, mt, *_: (mt[w], j)),
            scratch_shapes=[pltpu.VMEM((slots, k, tile_n), rhs.dtype),
                            pltpu.SemaphoreType.DMA((slots,))],
        ),
        out_shape=jax.ShapeDtypeStruct((m, n), lhs.dtype),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"), **limit),
        **kernel_names(kernel),
    )(gids, mtids, rs, re_, *make_block_metadata(group_sizes), lhs, rhs)
    # m-tiles past the last group are never visited (uninitialised) —
    # the contract is zeros there
    total = jnp.sum(group_sizes)
    return jnp.where(jnp.arange(m, dtype=jnp.int32)[:, None] < total,
                     out, 0)


# --------------------------------------------------------------------- #
# dlhs kernel: dlhs[M, K] = dout @ rhs[g]^T, accumulated over n-tiles
# --------------------------------------------------------------------- #
def _gmm_dlhs_kernel(group_ids, m_tile_ids, row_start, row_end, dout_ref,
                     rhs_ref, out_ref, acc_ref, *, tile_m: int,
                     n_tiles: int):
    w = pl.program_id(0)
    j = pl.program_id(1)
    mt = m_tile_ids[w]

    live = row_end[w] > row_start[w]     # a unit past num_work is skipped

    @pl.when(j == 0)
    def _():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    @pl.when(live)
    def _():
        # [tm, tn] @ [K, tn]^T -> [tm, K]
        acc_ref[:] += jax.lax.dot_general(
            dout_ref[:], rhs_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(j == n_tiles - 1)
    def _():
        @pl.when(jnp.logical_or(w == 0, m_tile_ids[w - 1] != mt))
        def _():
            out_ref[:] = jnp.zeros_like(out_ref)

        @pl.when(live)
        def _():
            rows = mt * tile_m + jax.lax.broadcasted_iota(
                jnp.int32, (tile_m, 1), 0)
            keep = (rows >= row_start[w]) & (rows < row_end[w])
            out_ref[:] = jnp.where(keep, acc_ref[:].astype(out_ref.dtype),
                                   out_ref[:])


@functools.partial(jax.jit, static_argnames=("tile_m", "tile_n",
                                             "interpret"))
def _gmm_dlhs_kernel_call(dout, rhs, group_sizes, tile_m: int, tile_n: int,
                          interpret: bool):
    m, n = dout.shape
    e, k, _ = rhs.shape
    gids, mtids, rs, re_, _ = make_group_metadata(group_sizes, m, tile_m)
    w = gids.shape[0]
    n_tiles = n // tile_n
    kernel = functools.partial(_gmm_dlhs_kernel, tile_m=tile_m,
                               n_tiles=n_tiles)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(w, n_tiles),
            in_specs=[
                pl.BlockSpec((tile_m, tile_n),
                             lambda w, j, g, mt, rs, re: (mt[w], j)),
                pl.BlockSpec((1, k, tile_n),
                             lambda w, j, g, mt, rs, re: (g[w], 0, j)),
            ],
            out_specs=pl.BlockSpec(
                (tile_m, k), lambda w, j, g, mt, rs, re: (mt[w], 0)),
            scratch_shapes=[pltpu.VMEM((tile_m, k), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((m, k), dout.dtype),
        interpret=interpret,
        **kernel_names(kernel),
    )(gids, mtids, rs, re_, dout, rhs)
    # gradient rows past the last group: never visited -> zeros by contract
    total = jnp.sum(group_sizes)
    out = jnp.where(jnp.arange(m, dtype=jnp.int32)[:, None] < total,
                    out, 0)
    return out


# --------------------------------------------------------------------- #
# drhs kernel ("tgmm"): drhs[E, K, N]; per group accumulate lhsᵀ @ dout
# over the group's work units.
# --------------------------------------------------------------------- #
def _gmm_drhs_kernel(group_ids, m_tile_ids, row_start, row_end, lhs_ref,
                     dout_ref, out_ref, acc_ref, *, tile_m: int,
                     num_work_static: int):
    j = pl.program_id(0)
    w = pl.program_id(1)
    g = group_ids[w]
    mt = m_tile_ids[w]
    new_group = jnp.logical_or(w == 0, group_ids[w - 1] != g)

    @pl.when(new_group)
    def _():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    # a unit past num_work accumulates nothing; the flush below stays
    # outside this guard: it fires at w == W - 1, which IS such a unit
    # whenever there is one
    @pl.when(row_end[w] > row_start[w])
    def _():
        rows = mt * tile_m + jax.lax.broadcasted_iota(
            jnp.int32, (tile_m, 1), 0)
        keep = (rows >= row_start[w]) & (rows < row_end[w])
        lhs_masked = jnp.where(keep, lhs_ref[:].astype(jnp.float32), 0.0)
        # [tm, K]^T @ [tm, tn] -> [K, tn]
        acc_ref[:] += jax.lax.dot_general(
            lhs_masked, dout_ref[:].astype(jnp.float32),
            (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    last_of_group = jnp.logical_or(
        w == num_work_static - 1,
        group_ids[jnp.minimum(w + 1, num_work_static - 1)] != g)

    @pl.when(last_of_group)
    def _():
        out_ref[0] = acc_ref[:].astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("tile_m", "tile_n",
                                             "interpret"))
def _gmm_drhs_kernel_call(lhs, dout, group_sizes, tile_m: int, tile_n: int,
                          interpret: bool):
    m, k = lhs.shape
    _, n = dout.shape
    e = group_sizes.shape[0]
    gids, mtids, rs, re_, _ = make_group_metadata(group_sizes, m, tile_m)
    w = gids.shape[0]
    kernel = functools.partial(_gmm_drhs_kernel, tile_m=tile_m,
                               num_work_static=w)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(n // tile_n, w),
            in_specs=[
                pl.BlockSpec((tile_m, k),
                             lambda j, w, g, mt, rs, re: (mt[w], 0)),
                pl.BlockSpec((tile_m, tile_n),
                             lambda j, w, g, mt, rs, re: (mt[w], j)),
            ],
            out_specs=pl.BlockSpec(
                (1, k, tile_n), lambda j, w, g, mt, rs, re: (g[w], 0, j)),
            scratch_shapes=[pltpu.VMEM((k, tile_n), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((e, k, n), lhs.dtype),
        interpret=interpret,
        **kernel_names(kernel),
    )(gids, mtids, rs, re_, lhs, dout)
    # empty groups' output blocks are never visited (uninitialised, can
    # hold NaN) — an expert that received no tokens has zero gradient;
    # `where` (not multiply) so 0 * NaN cannot leak through
    return jnp.where((group_sizes > 0)[:, None, None], out, 0)


# --------------------------------------------------------------------- #
# Reference composition (XLA): used for CPU and as the parity oracle.
# --------------------------------------------------------------------- #
def gmm_reference(lhs, rhs, group_sizes):
    m = lhs.shape[0]
    e = rhs.shape[0]
    ends = jnp.cumsum(group_sizes)
    starts = ends - group_sizes
    rows = jnp.arange(m, dtype=jnp.int32)[:, None]
    onehot = ((rows >= starts[None, :]) & (rows < ends[None, :])).astype(
        lhs.dtype)                                   # [M, E]
    return jnp.einsum("me,mk,ekn->mn", onehot, lhs, rhs,
                      preferred_element_type=jnp.float32).astype(lhs.dtype)


# --------------------------------------------------------------------- #
# Public differentiable entry
# --------------------------------------------------------------------- #
@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def gmm(lhs: jnp.ndarray, rhs: jnp.ndarray, group_sizes: jnp.ndarray,
        tile_m: int = 128, tile_n: int = 128,
        interpret: Optional[bool] = None) -> jnp.ndarray:
    """Grouped matmul: rows of ``lhs`` [M, K] (sorted by group) times
    per-group ``rhs`` [E, K, N]; ``group_sizes`` [E] sums to <= M (rows
    past the last group produce zeros).  M must be divisible by tile_m
    and N by tile_n on the kernel path; they are the FORWARD's tiles
    (``_pick_tiles``).  Differentiable (custom VJP: dlhs kernel + tgmm
    drhs kernel, at the tiles ``_pick_backward_tiles`` gives their
    larger working sets)."""
    return _gmm_impl(lhs, rhs, group_sizes, tile_m, tile_n, interpret)


def _use_kernel(interpret, m, n, tile_m, tile_n) -> Tuple[bool, bool]:
    """(run kernel composition, interpret mode).  interpret=None (the
    production default) runs the kernel on TPU only — on other backends
    the XLA reference composition is far faster than Python-level
    interpret-mode grid emulation; tests opt into interpret=True."""
    if m % tile_m != 0 or n % tile_n != 0:
        return False, False
    if interpret is None:
        return (True, False) if on_tpu() else (False, False)
    return True, bool(interpret)


def _gmm_impl(lhs, rhs, group_sizes, tile_m, tile_n, interpret):
    use, interp = _use_kernel(interpret, lhs.shape[0], rhs.shape[2],
                              tile_m, tile_n)
    if not use:
        return gmm_reference(lhs, rhs, group_sizes)
    return _gmm_fwd_kernel_call(lhs, rhs, group_sizes.astype(jnp.int32),
                                tile_m, tile_n, interp)


def _gmm_fwd(lhs, rhs, group_sizes, tile_m, tile_n, interpret):
    return (_gmm_impl(lhs, rhs, group_sizes, tile_m, tile_n, interpret),
            (lhs, rhs, group_sizes))


def _gmm_bwd(tile_m, tile_n, interpret, res, dout):
    lhs, rhs, group_sizes = res
    m, k = lhs.shape
    n = rhs.shape[2]
    # the forward's tiles say whether the kernels run at all; the backward
    # kernels get tiles of their own (their accumulators would overflow
    # the forward's): nothing but (lhs, rhs, group_sizes) is carried over,
    # each kernel call rebuilds its metadata from ``group_sizes``
    use, interp = _use_kernel(interpret, m, n, tile_m, tile_n)
    bm, bn = _pick_backward_tiles(m, k, n, lhs.dtype.itemsize)
    use = use and m % bm == 0 and n % bn == 0
    gs = group_sizes.astype(jnp.int32)
    if use:
        dlhs = _gmm_dlhs_kernel_call(dout, rhs, gs, bm, bn, interp)
        drhs = _gmm_drhs_kernel_call(lhs, dout, gs, bm, bn, interp)
    else:
        ends = jnp.cumsum(gs)
        starts = ends - gs
        rows = jnp.arange(m, dtype=jnp.int32)[:, None]
        onehot = ((rows >= starts[None, :]) & (rows < ends[None, :])
                  ).astype(lhs.dtype)
        dlhs = jnp.einsum("me,mn,ekn->mk", onehot, dout, rhs,
                          preferred_element_type=jnp.float32
                          ).astype(lhs.dtype)
        drhs = jnp.einsum("me,mk,mn->ekn", onehot, lhs, dout,
                          preferred_element_type=jnp.float32
                          ).astype(rhs.dtype)
    return dlhs, drhs, None


gmm.defvjp(_gmm_fwd, _gmm_bwd)


#: scoped VMEM budget for one kernel's working set — the TPU's default
#: limit is 16 MiB, which leaves the compiler 4 MiB beside the blocks
_VMEM_BUDGET = 12 * 1024 * 1024
_VMEM_HEADROOM = 4 * 1024 * 1024
#: what a forward whose N has no column tile between 128 and itself may
#: hold under a limit of its own (a v5e core has 128 MiB of VMEM)
_VMEM_RAISED_BUDGET = 2 * _VMEM_BUDGET

#: rows a bf16 weight block must multiply before its MXU passes take as
#: long as its DMA: 197 TFLOP/s over 819 GB/s (v5e; 2 FLOPs a row for each
#: 2-byte weight).  Under it the weight stream is the call's time IF the
#: next block streams in under all of this block's passes: the weight ring
#: sees to that (PR 53).  On the grid pipeline's one-step prefetch a pass
#: hid only under the DMA of the step right after it, and every pass of a
#: step that reused its block stood outside the stream whatever the rows.
#: A column tile under it leaves the row tiles' re-reads exposed.
_MXU_BOUND_ROWS = 240


def _forward_vmem(tile_m: int, k_dim: int, tile_n: int,
                  itemsize: int = 2, slots: int = 2) -> int:
    """Bytes of ``_gmm_kernel``'s working set: double-buffered lhs and out
    blocks and the weight ring's ``slots`` blocks (two are what the grid
    pipeline's double buffer held).  No accumulator: one dot covers the
    whole K."""
    return itemsize * (2 * tile_m * (k_dim + tile_n)
                       + slots * k_dim * tile_n)


def _ring_slots(tile_m: int, k_dim: int, tile_n: int,
                itemsize: int = 2) -> int:
    """Slots of the forward's weight ring: a third (the ring then runs two
    blocks ahead, and one expert of several units no longer stalls the
    stream behind it: -1.3 to -3.4% on the LFM2 ``T1152`` calls) where the
    working set with it still fits ``_VMEM_BUDGET``.  A call that is over
    the default budget at two (Moonlight's whole-N gate / up) stays at two:
    a third slot of 5.5 MiB under a limit raised further measured 3.7%
    SLOWER (my chip runs, PR 53, calls 1-2; PERF.md section 6)."""
    return 3 if _forward_vmem(tile_m, k_dim, tile_n, itemsize,
                              3) <= _VMEM_BUDGET else 2


def _pick_tiles(m_dim: int, k_dim: int, n_dim: int,
                groups: Optional[int] = None, itemsize: int = 2):
    """(tile_m, tile_n) of the FORWARD kernel for ``[m, K] x [groups, K,
    n]``, from static shapes alone.

    ``tile_m`` from the rows an expert holds, ``m / groups`` (an upper
    bound where the matrices are a share of the router's experts): a group
    costs ``rows + tile_m`` rows of MXU passes, its boundary tile being
    shared, and the passes hide under the NEXT expert's weight stream (the
    kernel's ring has it in flight under all of them) while that stays
    under ``_MXU_BOUND_ROWS``.  Only 128, the smallest tile,
    can stay under it, so 128 it is wherever groups are a few rows to a
    few hundred (a decode tick's 8 rows an expert under a 512-row tile
    multiply 520 rows for 8; over the bound every padded row is exposed
    MXU time, so the least padding still wins) and where ``groups`` is
    unknown.  A taller tile (the largest of 512, 256 dividing ``m``) is
    taken only where it is an eighth of the group or less: training's few
    wide experts with thousands of rows each, MXU-bound on their own rows,
    where padding is a few percent and the step count matters.

    ``tile_n`` is the widest lane-aligned divisor of ``n`` whose forward
    working set (``_forward_vmem`` at a ring of two slots, the footprint
    of the grid pipeline's double buffer: no accumulator) fits
    ``_VMEM_BUDGET``: it divides the number of grid steps and of re-reads
    of each row tile.  The ring takes a third slot afterwards, where
    ``_VMEM_BUDGET`` holds it (``_ring_slots``): the tiles never narrow
    for it.
    A row tile taller than 128 that leaves a column tile under
    ``_MXU_BOUND_ROWS`` gives way to the next smaller one; where 128 rows
    leave one too (Moonlight's N = 1408 = 11 x 128 has no lane-aligned
    divisor between 128 and itself: eleven walks over the work units, a
    0.5 MB sliver of weights a step) the whole N is the tile if it fits
    ``_VMEM_RAISED_BUDGET``, and the call brings the limit that takes
    (0.34 -> 0.20 ms a Moonlight gate / up call; PERF.md, PR 36).  The
    backward kernels never see these tiles (``_pick_backward_tiles``)."""
    rows = m_dim / groups if groups else 0
    widths = [tn for tn in range(n_dim - n_dim % 128, 0, -128)
              if n_dim % tn == 0]

    def widest(tm):
        return next((tn for tn in widths if _forward_vmem(
            tm, k_dim, tn, itemsize) <= _VMEM_BUDGET), 0)

    for tm in (512, 256):
        if m_dim % tm == 0 and 8 * tm <= rows \
                and widest(tm) >= _MXU_BOUND_ROWS:
            return tm, widest(tm)
    tn = widest(128)
    if m_dim % 128 or not tn:
        return 128, 128
    if tn < _MXU_BOUND_ROWS and _forward_vmem(
            128, k_dim, n_dim, itemsize) <= _VMEM_RAISED_BUDGET:
        tn = n_dim
    return 128, tn


def _pick_backward_tiles(m_dim: int, k_dim: int, n_dim: int,
                         itemsize: int = 2):
    """(tile_m, tile_n) both backward kernels run at: the largest
    ``tile_m`` dividing ``m``, then the widest ``tile_n`` dividing ``n``,
    whose working set fits ``_VMEM_BUDGET`` WITH the float32 accumulator
    on top of the three double-buffered blocks, the larger of dlhs's
    ``(tile_m, K)`` and drhs's ``(K, tile_n)``.  Tall row tiles first: dlhs re-reads a group's
    weights for every work unit, so its steps want as many rows as a
    weight block can pay for, and a backward's groups are a training
    batch's."""
    for tm in (512, 256, 128):
        if m_dim % tm:
            continue
        for tn in (1024, 896, 768, 640, 512, 384, 256, 128):
            need = (_forward_vmem(tm, k_dim, tn, itemsize)
                    + 4 * max(tm * k_dim, k_dim * tn))
            if n_dim % tn == 0 and need <= _VMEM_BUDGET:
                return tm, tn
    return 128, 128


def exact_topk_routing(logits: jnp.ndarray, k: int,
                       renormalize: bool = True):
    """Dropless router: softmax over all experts -> top-k -> weights,
    renormalised to sum to one (``renormalize``, static; HF Mixtral
    semantics, the default) or as the softmax gave them (HF
    ``norm_topk_prob: false``, OLMoE).  The single source of truth shared
    by the training gate (moe/sharded_moe.py), the ragged inference path
    (modules/moe.py), and benchmarks.  Returns (topi [T,k] int32,
    topw [T,k] fp32)."""
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    topw, topi = jax.lax.top_k(probs, k)
    if renormalize:
        topw = topw / jnp.maximum(jnp.sum(topw, axis=-1, keepdims=True),
                                  1e-9)
    return topi.astype(jnp.int32), topw


def sigmoid_bias_topk_routing(logits: jnp.ndarray, bias: jnp.ndarray,
                              k: int, renormalize: bool = True,
                              scale: float = 1.0, norm_eps: float = 1e-20):
    """Dropless router of the DeepSeek-V3 family (``scoring_func:
    sigmoid``, ``topk_method: noaux_tc``, one group): ``s =
    sigmoid(logits)`` over all experts; the experts are the top-k of ``s +
    bias`` (the learned selection bias); their weights are ``s`` at the
    chosen experts, WITHOUT the bias, divided by their sum plus
    ``norm_eps`` (``renormalize``; the published codes differ in the
    constant: 1e-20 for DeepSeek-V3, 1e-6 for LFM2) and multiplied by
    ``scale`` (``routed_scaling_factor``).  Returns (topi
    [T,k] int32, topw [T,k] fp32)."""
    s = jax.nn.sigmoid(logits.astype(jnp.float32))
    _, topi = jax.lax.top_k(s + bias.astype(jnp.float32), k)
    topw = jnp.take_along_axis(s, topi, axis=-1)
    if renormalize:
        topw = topw / (jnp.sum(topw, axis=-1, keepdims=True) + norm_eps)
    return topi.astype(jnp.int32), topw * scale


def softmax_bias_topk_routing(logits: jnp.ndarray, bias: jnp.ndarray,
                              k: int, scale: float = 1.0):
    """Dropless router of the LongCat-Flash family: ``s = softmax(logits)``
    over EVERY output of the router (its experts and its zero-compute
    experts alike); the chosen outputs are the top-k of ``s + bias`` (the
    learned selection bias; ties to the lowest index); their weights are
    ``s`` at the chosen outputs, WITHOUT the bias and without
    renormalisation, times ``scale`` (``routed_scaling_factor``).  Returns
    (topi [T,k] int32, topw [T,k] fp32)."""
    s = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    _, topi = jax.lax.top_k(s + bias.astype(jnp.float32), k)
    topw = jnp.take_along_axis(s, topi, axis=-1)
    return topi.astype(jnp.int32), topw * scale


# --------------------------------------------------------------------- #
# Dropless MoE FFN on top of gmm: sort-by-expert (★moe_scatter), three
# grouped GEMMs (SwiGLU), unsort+combine (★moe_gather).
# --------------------------------------------------------------------- #
def grouped_moe_ffn(x: jnp.ndarray, topi: jnp.ndarray, topw: jnp.ndarray,
                    w_gate: jnp.ndarray, w_up: jnp.ndarray,
                    w_down: jnp.ndarray,
                    interpret: Optional[bool] = None,
                    expert_start: Optional[int] = None) -> jnp.ndarray:
    """x: [T, H]; topi/topw: [T, k] routing; w_gate/w_up: [E, H, F],
    w_down: [E, F, H].  Returns [T, H].  FLOPs scale with k·T (not E·T):
    tokens are sorted by expert and each expert multiplies only its own
    contiguous row block.

    ``expert_start`` (static): the matrices are ONE SHARE of a wider
    router's experts, ``[expert_start, expert_start + E)`` of the ids in
    ``topi``.  Rows routed elsewhere leave before the counting sort (the
    groups then sum to fewer than ``T x k`` rows, which the kernel's
    work-unit list allows) and weigh nothing in the combine: the result is
    the part of the expert sum this share gives."""
    t, h = x.shape
    e = w_gate.shape[0]
    k = topi.shape[1]
    f = w_gate.shape[2]
    # device scopes: moe/dispatch (sort + gather), moe/experts (the three
    # grouped GEMMs and the SwiGLU product), moe/combine (unsort + weight)
    with jax.named_scope("moe/dispatch"):
        flat_e = topi.reshape(-1).astype(jnp.int32)          # [T*k]
        if expert_start is not None:
            flat_e = flat_e - expert_start
            held = (flat_e >= 0) & (flat_e < e)
            # -1 has no one-hot column: counted in no group, ranked nowhere
            flat_e = jnp.where(held, flat_e, -1)
            topw = jnp.where(held.reshape(topw.shape), topw, 0)
        # counting sort by expert (stable): XLA's general sort is far
        # slower than a one-hot cumsum at these sizes (measured ~0.7 ms
        # for an argsort-based sort/gather stage at M=4096 on v5e)
        oh = jax.nn.one_hot(flat_e, e, dtype=jnp.int32)      # [M, E]
        group_sizes = jnp.sum(oh, axis=0)
        within = jnp.cumsum(oh, axis=0) - oh
        rank = jnp.take_along_axis(within, flat_e[:, None], 1)[:, 0]
        offsets = jnp.cumsum(group_sizes) - group_sizes
        dest = offsets[flat_e] + rank                    # [M] sorted slot
        m_rows = flat_e.shape[0]
        if expert_start is not None:
            # past the end: dropped by the scatter below, and the combine's
            # gather of it (clamped) is weighed by zero
            dest = jnp.where(held, dest, m_rows)
        order = jnp.zeros((m_rows,), jnp.int32).at[dest].set(
            jnp.arange(m_rows, dtype=jnp.int32))
        xs = x[order // k]                               # [T*k, H] sorted
        # whole row tiles for the kernel (its smallest is 128 rows): a
        # token count that is no multiple of 128 / k would otherwise fall
        # to the XLA composition; rows past the last group come out zero
        # and nothing below reads them
        m_pad = -(-m_rows // 128) * 128
        if m_pad != m_rows:
            xs = jnp.pad(xs, ((0, m_pad - m_rows), (0, 0)))

    with jax.named_scope("moe/experts"):
        size = x.dtype.itemsize
        tm_g, tn_g = _pick_tiles(m_pad, h, f, e, size)
        gate = gmm(xs, w_gate, group_sizes, tm_g, tn_g, interpret)
        up = gmm(xs, w_up, group_sizes, tm_g, tn_g, interpret)
        hmid = (jax.nn.silu(gate.astype(jnp.float32))
                * up.astype(jnp.float32)).astype(x.dtype)
        tm_d, tn_d = _pick_tiles(m_pad, f, h, e, size)
        down = gmm(hmid, w_down, group_sizes, tm_d, tn_d,
                   interpret)                            # [m_pad, H] sorted
    with jax.named_scope("moe/combine"):
        # unsort by a GATHER (``dest`` is a permutation: row j = token
        # j // k, choice j % k sits at sorted slot dest[j]) and sum each
        # token's k weighted rows.  A scatter-add over the token index
        # serialises on the TPU: ten layers at H = 2048, k = 8 on a v5e
        # took 6.58 ms against 1.98 at 1056 tokens, 2.38 against 1.07 at
        # 544, 0.22 against 0.23 at 32 (PERF.md, PR 25, chip call 4).
        # The gather is CHOICE-major, ``[k, T, H]``: slab j holds every
        # token's j-th choice with T and H where ``down`` has them, and the
        # k terms are written out so that the widening to float32 stays
        # inside the one fusion that reads the slabs once in ``down``'s
        # dtype and writes ``[T, H]`` once.  Token-major (``[T, k, H]`` and
        # a sum over axis 1) puts k on the sublanes; at a k that is no whole
        # tile XLA then writes a float32 relayout of all T x k rows in front
        # of the reduce (compiled for a v5e, temporaries / XLA's own cycles
        # before -> now: 396.5 MB / 1,402,990 + the relayout -> 94.5 MB /
        # 290,879 at (T, k, H) = (1152, 10, 4096); 138.5 MB / 687,414 + the
        # relayout -> 0 / 141,163 at (1056, 10, 2048); 0 / 14,532 -> 0 /
        # 11,325 at (32, 8, 2048); on the chip, a layer's combine alone:
        # 1,311 -> 412 us, 522 -> 160, 5.5 -> 4.8; k gathers of [T, H]
        # read 409 / 131 / 10.2, slower than before at every decode shape;
        # PERF.md, PR 60, chip call 1).  A held-out slot's
        # ``dest`` is ``m_rows``: its gather is clamped to ``down``'s last
        # row, a real row or one of the rows past the last group, which the
        # kernel writes as zeros, and its weight is zero: nothing that is
        # not a number enters through it
        rows = down[dest.reshape(t, k).T]                # [k, T, H]
        w = topw.astype(jnp.float32)
        acc = rows[0].astype(jnp.float32) * w[:, 0, None]
        for j in range(1, k):
            acc = acc + rows[j].astype(jnp.float32) * w[:, j, None]
        return acc.astype(x.dtype)


# --------------------------------------------------------------------- #
# dslint contract-checker registration (see analysis/pallas_lint.py):
# the kernel_selftest shapes incl. an empty expert group, invoked under
# the checker's capture context — no kernel body runs.
# --------------------------------------------------------------------- #
from deepspeed_tpu.analysis.registry import pallas_kernel_case  # noqa: E402


def _dslint_gmm_inputs():
    import numpy as np

    rng = np.random.default_rng(1)
    lhs = jnp.asarray(rng.standard_normal((512, 256)).astype(np.float32),
                      jnp.bfloat16)
    rhs = jnp.asarray(rng.standard_normal((4, 256, 256)).astype(np.float32),
                      jnp.bfloat16)
    sizes = jnp.asarray([128, 256, 0, 128], jnp.int32)
    return lhs, rhs, sizes


@pallas_kernel_case("gmm_fwd",
                    note="grouped expert GEMM forward, selftest sizes "
                         "with an empty group, two n-tiles; seven "
                         "scalar-prefetch arrays (the work units' four, "
                         "the weight ring's walk: block_of, next_live, "
                         "num_blocks); the expert weights stay in HBM "
                         "(memory_space=ANY) and a ring of three "
                         "[K, tile_n] blocks with a DMA semaphore a slot "
                         "is the kernel's scratch, counted by the VMEM "
                         "rule beside the double-buffered lhs / out")
def _dslint_gmm_fwd():
    lhs, rhs, sizes = _dslint_gmm_inputs()
    gmm(lhs, rhs, sizes, 128, 128, True)


@pallas_kernel_case("gmm_dlhs", note="grouped GEMM dlhs backward")
def _dslint_gmm_dlhs():
    lhs, rhs, sizes = _dslint_gmm_inputs()
    dout = jnp.zeros((512, 256), jnp.bfloat16)
    _gmm_dlhs_kernel_call(dout, rhs, sizes, 128, 128, True)


@pallas_kernel_case(
    "gmm_drhs",
    allow=("pallas-uncovered-tile",),
    note="tgmm drhs backward; an EMPTY expert group legitimately leaves "
         "its output block unwritten — masked by the jnp.where in "
         "_gmm_drhs_kernel_call, so the uncovered-tile rule is waived")
def _dslint_gmm_drhs():
    lhs, rhs, sizes = _dslint_gmm_inputs()
    dout = jnp.zeros((512, 256), jnp.bfloat16)
    _gmm_drhs_kernel_call(lhs, dout, sizes, 128, 128, True)
