"""Learned sparse attention over a paged pool of latent rows (DeepSeek Sparse
Attention, ``model_type: glm_moe_dsa``): beside the latent row ``[c | k_pe |
0]`` of ``latent_flash.py`` every token keeps a second, narrow row, its
**indexer key** (``index_head_dim`` values = one 128-lane tile), behind the
same block table.  A query row first scores EVERY cached position of its
sequence against that narrow row,

    I[t, s] = sum_j w[t, j] * relu(qI[t, j] . kI[s])        (float32),

takes the exact top ``index_topk`` of them (ties to the lowest position) and
attends over those latent rows alone.  Three steps.  The scores, the
selection and the one-token rows' read are XLA compositions that walk a
row's block table in steps of :data:`KEY_BLOCK` positions up to the furthest
position any row of the call holds (a ``while`` loop: nothing past it is
read, and nothing of the pool but through a table); the read of a prompt
chunk's tile rows, most of a mixed tick, is one Mosaic kernel on a TPU
(:func:`sparse_tile_read`) and the composition it is tested against
elsewhere (:func:`masked_latent_read`):

* :func:`index_scores`: the walk over the narrow leaf, 256 B a position;
  what lies past a row's own position scores ``-inf``.
* the selection, exact, two ways.  **Rows that share a context** (the
  tiles of a prompt chunk): :func:`select_threshold`, a radix select on the
  scores' bits, finds each row's ``k``-th largest score (16 passes over the
  scores, two bits each) and, where several positions tie on it, the
  position up to which the ties are taken (8 passes); the set is
  then ``score > t or (score == t and position <= cut)``, which the read
  applies as a mask, block by block (:func:`masked_latent_read`: all of a
  tile's rows against the same latent block in ONE product, absorbed;
  :func:`sparse_tile_read` is the same sums with the float32 scores kept
  in VMEM, the pool's blocks fetched in place, and each tile's walk ended
  at its own last position: the note above ``_sparse_tile_read_kernel``).
  A sort of a chunk's 1,024 x 33 k scores would cost several times the
  attention it saves.  **One-token rows** have no one to share a block
  with: ``lax.top_k`` (stable: the lower index first among equals) gives
  their positions, and :func:`gathered_latent_read` reads those rows
  token-granular through the table, 2,048 rows of 1,280 B a row and layer
  where the dense walk would read them all.
* both reads are the absorbed form (``W_uk`` folded into the query, ``W_uv``
  applied to the output by the caller), online softmax in float32.

The mathematics of both reads is the sum over the selected set and nothing
else; a position past ``min(k, p + 1)`` never enters it.
"""

from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.inference.v2.kernels.blocked_flash import (
    _PREFILL_VMEM_BUDGET, _PREFILL_VMEM_HEADROOM)
from deepspeed_tpu.utils.platform import kernel_names, on_tpu

F32 = jnp.float32
LANES = 128
#: context positions one step of a walk covers (whole blocks of a table)
KEY_BLOCK = 2048
_NEG = -1e30


def _steps(tables, block_size: int):
    """``(table entries a step, tables padded to whole steps)``.  The pad
    entries name block 0, the allocator's trash block; every position they
    stand for is past any row's own and masked by it."""
    width = tables.shape[1]
    nblk = min(max(1, KEY_BLOCK // block_size), width)
    pad = -width % nblk
    return nblk, jnp.pad(tables, ((0, 0), (0, pad)))


def _step_rows(pool, tables, j, nblk: int, block_size: int):
    """Pool rows of step ``j`` of each group's table, whole blocks at a
    time: [G, nblk * bs, W]."""
    blk = lax.dynamic_slice_in_dim(tables, j * nblk, nblk, 1)
    rows = pool.reshape(-1, block_size, pool.shape[-1])[blk]
    return rows.reshape(tables.shape[0], nblk * block_size, pool.shape[-1])


def _live_steps(pos, span: int):
    """Steps of ``span`` positions up to the furthest position (0: none)."""
    return jnp.maximum(jnp.max(pos), -1) // span + 1


def index_scores(q_idx, w_idx, idx_pool, tables, pos, *, block_size: int):
    """``I`` of the module doc for ``G`` groups of ``R`` rows that share a
    table: ``q_idx [G, R, HI, DI]``, ``w_idx [G, R, HI]`` (float32),
    ``idx_pool [rows, DI]``, ``tables [G, B]``, ``pos [G, R]`` (-1: a pad
    row).  Returns float32 ``[G, R, C]``, ``C`` the table's positions up to
    whole steps, ``-inf`` past a row's position."""
    g, r = pos.shape
    nblk, tables = _steps(tables, block_size)
    span = nblk * block_size
    offs = jnp.arange(span, dtype=jnp.int32)
    q_idx = q_idx.astype(idx_pool.dtype)

    def step(j, out):
        keys = _step_rows(idx_pool, tables, j, nblk, block_size)
        s = jnp.einsum("grjd,gkd->grjk", q_idx, keys,
                       preferred_element_type=F32)
        s = jnp.einsum("grjk,grj->grk", jax.nn.relu(s), w_idx,
                       preferred_element_type=F32)
        # (-0.0 and 0.0 are one score: the selection compares bits)
        s = jnp.where(s == 0.0, 0.0, s)
        keep = (j * span + offs)[None, None, :] <= pos[:, :, None]
        return lax.dynamic_update_slice_in_dim(
            out, jnp.where(keep, s, -jnp.inf), j * span, 2)

    out = jnp.full((g, r, tables.shape[1] * block_size), -jnp.inf, F32)
    return lax.fori_loop(0, _live_steps(pos, span), step, out)


def sort_key(scores):
    """float32 -> uint32 whose unsigned order is the floats' order."""
    bits = lax.bitcast_convert_type(scores, jnp.uint32)
    return jnp.where(bits >> 31 == 1, ~bits, bits | jnp.uint32(1 << 31))


def _radix(count_at_least, digits: int, init):
    """The largest value ``v`` of ``2 * digits`` bits with
    ``count_at_least(v)`` true (``v = 0`` always is), two bits a pass: the
    three candidates of a pass are counted in one read of the data."""
    def digit(i, v):
        shift = (2 * (digits - 1 - i)).astype(v.dtype)
        best = v
        for j in (1, 2, 3):         # counts fall with j: the last that holds
            cand = v | (jnp.asarray(j, v.dtype) << shift)
            best = jnp.where(count_at_least(cand), cand, best)
        return best

    return lax.fori_loop(0, digits, digit, init)


def _select(key, k: int):
    n, c = key.shape
    count = lambda mask: jnp.sum(mask, axis=1, dtype=jnp.int32)
    thr = _radix(lambda cand: count(key >= cand[:, None]) >= k, 16,
                 jnp.zeros((n,), jnp.uint32))
    need = k - count(key > thr[:, None])
    tie = key == thr[:, None]
    place = jnp.arange(c, dtype=jnp.int32)[None, :]
    # the largest ``cut`` with fewer than ``need`` ties before it: the
    # position of the ``need``-th tie
    cut = _radix(lambda cand: count(tie & (place < cand[:, None])) < need,
                 max(1, ((c - 1).bit_length() + 1) // 2),
                 jnp.zeros((n,), jnp.int32))
    return thr, cut


def select_threshold(key, k: int, live=None):
    """Each row's exact top-``k`` set of ``key [N, C]`` (:func:`sort_key`
    of its scores) as ``(thr uint32 [N], cut int32 [N])``: the set is ``key
    > thr or (key == thr and position <= cut)`` (:func:`selected`), ties
    on the ``k``-th largest score taken from the lowest position.  A row
    with fewer than ``k`` entries selects them all.  A radix select on the
    keys' bits: 16 passes for the value, 8 for the position.  ``live``
    (traced): the positions that hold a score at all (the rest are
    ``-inf``); the passes then read the narrowest of a few static widths
    that holds them."""
    c = key.shape[1]
    widths = [w for w in (c // 8, c // 4, c // 2) if w >= 2 * k] + [c]
    if live is None or len(widths) == 1:
        return _select(key, k)
    which = sum((live > w).astype(jnp.int32) for w in widths[:-1])
    return lax.switch(which, [
        (lambda key, w=w: _select(key[:, :w], k)) for w in widths], key)


def selected(key, place, thr, cut):
    """The mask of :func:`select_threshold`'s set over ``key [..., C']`` at
    positions ``place [C']``."""
    thr, cut = thr[..., None], cut[..., None]
    return (key > thr) | ((key == thr) & (place <= cut))


def select_topk(scores, k: int):
    """Positions of each row's top ``k`` scores ``[N, C] -> int32 [N,
    min(k, C)]``, the lower position first among equal scores.  Entries a
    row does not have (fewer than ``k`` positions) come last and lie past
    its own position: the read masks them by it."""
    n, c = scores.shape
    if c <= k:
        return jnp.broadcast_to(jnp.arange(c, dtype=jnp.int32), (n, c))
    return lax.top_k(scores, k)[1].astype(jnp.int32)


def _finish(acc, total):
    return acc / jnp.maximum(total, 1e-30)[..., None]


def masked_latent_read(q_cat, pool, tables, pos, key, thr, cut, *,
                       block_size: int, rank: int, scale: float):
    """The absorbed read of ``G`` groups of ``R`` rows over their selected
    sets, given as :func:`select_threshold`'s mask: ``q_cat [G, R, H, W]``,
    ``pool [rows, W]``, ``key [G, R, C]``, ``thr`` / ``cut`` ``[G, R]``.
    Returns ``sum p c`` ``[G, R, H, rank]`` in float32."""
    g, r, h, _ = q_cat.shape
    nblk, tables = _steps(tables, block_size)
    span = nblk * block_size
    offs = jnp.arange(span, dtype=jnp.int32)
    q_cat = q_cat.astype(pool.dtype)

    def step(j, carry):
        m_prev, l_prev, acc = carry
        ctx = _step_rows(pool, tables, j, nblk, block_size)
        s = jnp.einsum("grhw,gkw->grhk", q_cat, ctx,
                       preferred_element_type=F32) * scale
        place = j * span + offs
        keep = selected(lax.dynamic_slice_in_dim(key, j * span, span, 2),
                        place, thr, cut) & (place <= pos[..., None])
        keep = keep[:, :, None, :]
        m_new = jnp.maximum(m_prev, jnp.max(jnp.where(keep, s, _NEG), -1))
        p = jnp.where(keep, jnp.exp(s - m_new[..., None]), 0.0)
        corr = jnp.exp(m_prev - m_new)
        acc = acc * corr[..., None] + jnp.einsum(
            "grhk,gkc->grhc", p.astype(ctx.dtype), ctx[..., :rank],
            preferred_element_type=F32)
        return m_new, l_prev * corr + jnp.sum(p, -1), acc

    init = (jnp.full((g, r, h), _NEG, F32), jnp.zeros((g, r, h), F32),
            jnp.zeros((g, r, h, rank), F32))
    _, total, acc = lax.fori_loop(0, _live_steps(pos, span), step, init)
    return _finish(acc, total)


def gathered_latent_read(q_cat, pool, tables, pos, idx, *, block_size: int,
                         rank: int, scale: float):
    """The absorbed read of ``G`` one-token rows over the positions ``idx
    [G, K]`` of :func:`select_topk`, each read through the row's table:
    ``q_cat [G, H, W]``, ``tables [G, B]``, ``pos [G]``.  Returns ``sum p
    c`` ``[G, H, rank]`` in float32."""
    blk = jnp.take_along_axis(tables, idx // block_size, axis=1)
    ctx = pool[blk * block_size + idx % block_size]            # [G, K, W]
    s = jnp.einsum("ghw,gkw->ghk", q_cat.astype(pool.dtype), ctx,
                   preferred_element_type=F32) * scale
    keep = (idx <= pos[:, None])[:, None, :]
    m = jnp.max(jnp.where(keep, s, _NEG), -1, keepdims=True)
    p = jnp.where(keep, jnp.exp(s - m), 0.0)
    acc = jnp.einsum("ghk,gkc->ghc", p.astype(ctx.dtype), ctx[..., :rank],
                     preferred_element_type=F32)
    return _finish(acc, jnp.sum(p, -1))


# ===================================================================== #
# The tile rows' read as one Mosaic kernel
# ===================================================================== #
#: context positions a key step of the kernel covers (whole blocks of a
#: table) and the heads whose rows share a step's fetch and its products
_STEP_KEYS = 256
_HEAD_GROUP = 16


def sparse_tile_read_usable(rank: int, width: int, block_size: int) -> bool:
    """Can :func:`sparse_tile_read` tile this geometry?  (Otherwise the
    model takes :func:`masked_latent_read`, as it does off the TPU.)"""
    return (rank % LANES == 0 and width % LANES == 0
            and block_size % 16 == 0)


def _tile_step_blocks(block_size: int, entries: int) -> int:
    """Table entries a key step of the kernel meets."""
    return min(max(1, _STEP_KEYS // block_size), entries)


def sparse_tile_key_steps(chunks, tiles: int, *, block_size: int,
                          entries: int, tile_q: int) -> tuple:
    """(key steps the tables of ONE :func:`sparse_tile_read` call hold,
    those of them at or before a tile's last position: the ones its walk
    runs) for a tile segment of ``tiles`` tiles that holds ``chunks``
    ((start, tokens) each, tile-aligned): host arithmetic on the rule the
    call takes its step from, for the ``engine/build_batch`` span."""
    keys = _tile_step_blocks(block_size, entries) * block_size
    live = sum((min(lo + tile_q, start + tokens) - 1) // keys + 1
               for start, tokens in chunks
               for lo in range(start, start + tokens, tile_q))
    return tiles * -(-entries * block_size // keys), live


# --------------------------------------------------------------------- #
# The grid is (tiles, groups of ``_HEAD_GROUP`` heads); a (tile, group) walks
# the tile's table in a loop of its own, ``_STEP_KEYS`` positions a step, up
# to the TILE's last position: a pad tile (-1) runs no step, and a short
# context beside a long one stops where it ends.  A step's operands come by
# double-buffered DMA, started under the step before (the first step of a
# (tile, group) under the last of the one before it; pad tiles are stepped
# over): the step's whole blocks of the pool IN PLACE through the
# scalar-prefetched table, and the step's slice of the tile's ``key``.  The
# queries and the result are head-major (``[H, G, R, .]``: the layout XLA
# gives both neighbours, the ``q_lat`` and ``W_uv`` products batched by
# head, so the transposes around the call compile to bitcasts and neither
# side pays a relayout), so a group's block is ``[heads, R, W]`` and its
# heads fold into the rows of both products with no copy: ``[16 x 128, 640]
# . [256, 640]^T`` streams 2,048 rows a weight tile.  The mask
# (``selected(...) & place <= pos``) is built once a (row, key) and shared
# by the group's heads (broadcast over the scores' leading axis); the keys'
# unsigned order is compared as a signed one (sign bit flipped on both
# sides).  The softmax update, ONE for the group's heads a step, is
# ``masked_latent_read``'s to the letter (``_NEG``, ``exp``, ``p`` cast to
# the pool's dtype for ``p . c``), with the statistics lane-wise as
# ``latent_flash._latent_prefill_kernel`` keeps them (``m`` in all 128
# lanes, ``l`` 128 partial sums, summed at the write-out).  Scores,
# statistics and accumulator are float32 and stay in VMEM: nothing of shape
# ``[.., keys]`` is written to HBM.
#
# Arithmetic at the published GLM-5 widths (64 heads, 640-lane row, rank
# 512, 8 tiles of 128 rows): a step of 256 keys of a (tile, group) multiplies
# 2,048 x 256 x 1,152 x 2 = 1.2 GFLOP = 6.1 us at the v5e's bf16 peak
# against 0.46 MB fetched (0.6 us): MXU-bound, and the context read again a
# group costs nothing visible.  Measured (PR 51, call 5, one layer, a chunk
# of 1,024 rows): 7.3 ms at 8 k positions and 28.3 ms at 32 k where the
# composition takes 13.7 and 48.2 (float32 scores through HBM four times a
# step, every tile walked in steps of 2,048 to the call's furthest
# position); 3.0 against 10.7 where the end of one prompt shares the call
# with the start of the next: 85-90% of the MXU's peak on what it
# multiplies.  Keys a step 256 / 512 / 1,024: 7.3 / 7.8 / 8.2 ms at 8 k (a
# tile's last step is rounded up less), 28.3 / 29.1 / 29.8 at 32 k; groups
# of 8 / 16 / 32 heads within 3%.  Calls 1 and 3 took the update a head,
# written out: the group's rows in one product read the same as four
# products of four heads and 2-3% faster than a loop over them (two heads a
# product 5-10% slower: 256 rows a weight tile); the update for all heads at
# once traces a sixteenth of the operations and reads within 1% at 8 k, 4%
# slower at 32 k at 512 keys.  What the kernel multiplies is every causal
# pair in the absorbed form: 4.3 x the selected pairs at 2.25 x the FLOPs a
# pair, so the share of the SELECTED pairs' roofline cannot pass ~10%
# (PERF.md section 7 has what would).  The wrapper is jitted for the
# trace's sake: a step program calls it a layer, and the kernel is traced
# and lowered once a program, not five times (0.2 s a call site: the cell's
# set-up read 4.8 s longer without).
# --------------------------------------------------------------------- #
def _sparse_tile_read_kernel(tables, tile_hi, q_ref, info_ref, key_hbm,
                             pool_hbm, o_ref, buf, kbuf, sems, acc_ref,
                             m_ref, l_ref, half_ref, *, block_size, scale,
                             rank):
    t, g = pl.program_id(0), pl.program_id(1)
    tiles, groups = pl.num_programs(0), pl.num_programs(1)
    entries = tables.shape[1]
    nblk = buf.shape[1]                   # table entries a step
    keys = nblk * block_size
    heads, _, rows, width = q_ref.shape   # of this group; a tile's rows
    w = m_ref.shape[-1]                   # lanes of a row's statistics

    def steps_of(tile):
        return (tile_hi[tile] + keys) // keys         # 0 on a pad tile (-1)

    def first_live(tile):
        """The first tile at or after ``tile`` with a step (``tiles``:
        none)."""
        return lax.while_loop(
            lambda r: jnp.logical_and(
                r < tiles, tile_hi[jnp.minimum(r, tiles - 1)] < 0),
            lambda r: r + 1, tile)

    def copies(tile, j, half, live, act):
        """Step ``j`` of ``tile``: its blocks of the pool, its slice of
        the keys.  Entries past the table's end name its last block: every
        position they stand for is past any row's own."""
        def both():
            for u in range(nblk):
                blk = tables[tile, jnp.minimum(j * nblk + u, entries - 1)]
                act(pltpu.make_async_copy(
                    pool_hbm.at[blk], buf.at[half, u], sems.at[half, 0]))
            act(pltpu.make_async_copy(
                key_hbm.at[tile, :, pl.ds(pl.multiple_of(j * keys, keys),
                                          keys)],
                kbuf.at[half], sems.at[half, 1]))

        if live is True:
            both()
        else:
            pl.when(live)(both)

    @pl.when(jnp.logical_and(t == 0, g == 0))
    def _():
        half_ref[0] = 0
        t0 = first_live(0)
        copies(jnp.minimum(t0, tiles - 1), 0, 0, t0 < tiles,
               lambda c: c.start())

    steps = steps_of(t)
    half0 = half_ref[0]
    # what follows this (tile, group)'s last step: the tile's first step
    # again for the next group, else the next live tile's
    after = jnp.where(g + 1 < groups, t, first_live(t + 1))

    @pl.when(steps > 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)

    thr = info_ref[0, :, 0:1]
    cut = info_ref[0, :, 1:2]
    pos = info_ref[0, :, 2:3]
    offs = lax.broadcasted_iota(jnp.int32, (rows, keys), 1)
    sign = jnp.int32(-2 ** 31)

    def fold(x, op, over_row):
        """[heads, rows, keys] -> [heads, rows, w]: ``op`` over the keys'
        lane tiles (w 1: ``over_row``, the same reduction over the whole
        row)."""
        if w == 1:
            return over_row(x, axis=2, keepdims=True)
        return functools.reduce(
            op, [x[:, :, c * w:(c + 1) * w] for c in range(keys // w)])

    def spread(x, n):
        """[heads, rows, w] -> [heads, rows, n]: the row's value in every
        lane."""
        if w == 1 or n == w:
            return x
        return x[:, :, :n] if n < w else jnp.tile(x, (1, 1, n // w))

    def body(i, carry):
        half = lax.rem(half0 + i, 2)
        last = i + 1 == steps
        copies(jnp.minimum(jnp.where(last, after, t), tiles - 1),
               jnp.where(last, 0, i + 1), 1 - half,
               jnp.logical_or(jnp.logical_not(last), after < tiles),
               lambda c: c.start())
        copies(t, i, half, True, lambda c: c.wait())
        ctx = buf.at[half].reshape(keys, width)[...]
        # the selected set's mask, once a (row, key): the keys' unsigned
        # order as a signed one
        key = lax.bitcast_convert_type(kbuf[half], jnp.int32) ^ sign
        place = i * keys + offs
        keep = ((key > thr) | ((key == thr) & (place <= cut))) \
            & (place <= pos)
        # the group's heads folded into the rows of both products; between
        # them one softmax update for all of them, the mask shared
        s = (lax.dot_general(
            q_ref[:, 0].reshape(heads * rows, width), ctx,
            (((1,), (1,)), ((), ())), preferred_element_type=F32)
            * scale).reshape(heads, rows, keys)
        keep = keep[None]
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(
            fold(jnp.where(keep, s, _NEG), jnp.maximum, jnp.max),
            axis=2, keepdims=True))
        p = jnp.where(keep, jnp.exp(s - spread(m_new, keys)), 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + fold(p, jnp.add, jnp.sum)
        m_ref[...] = m_new
        pv = lax.dot_general(
            p.astype(ctx.dtype).reshape(heads * rows, keys), ctx[:, :rank],
            (((1,), (0,)), ((), ())), preferred_element_type=F32)
        acc_ref[...] = acc_ref[...] * spread(corr, rank) \
            + pv.reshape(heads, rows, rank)
        return carry

    lax.fori_loop(0, steps, body, 0)
    half_ref[0] = lax.rem(half0 + steps, 2)

    @pl.when(steps > 0)
    def _():
        total = jnp.sum(l_ref[...], axis=2, keepdims=True)
        o_ref[:, 0] = (acc_ref[...] / jnp.maximum(total, 1e-30)).astype(
            o_ref.dtype)

    @pl.when(steps == 0)
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)


@functools.partial(jax.jit, static_argnames=("block_size", "rank", "scale",
                                             "interpret"))
def sparse_tile_read(q_cat, pool, tables, pos, key, thr, cut, *,
                     block_size: int, rank: int, scale: float,
                     interpret: Any = None):
    """:func:`masked_latent_read` as one Mosaic kernel: the same arguments,
    the same sums, the result in the queries' dtype (what the caller casts
    the composition's float32 to).  The note above
    ``_sparse_tile_read_kernel`` says how it walks."""
    g, r, h, width = q_cat.shape
    if interpret is None:
        interpret = not on_tpu()
    tables = tables.astype(jnp.int32)
    entries = tables.shape[1]
    nb = pool.shape[0] // block_size
    nblk = _tile_step_blocks(block_size, entries)
    keys = nblk * block_size
    steps = -(-entries // nblk)
    if key.shape[2] < steps * keys:
        key = jnp.pad(key, ((0, 0), (0, 0), (0, steps * keys - key.shape[2])))
    hg = max(d for d in range(1, _HEAD_GROUP + 1) if h % d == 0)
    pos = pos.astype(jnp.int32)
    # a row's threshold in the signed order the kernel compares in
    thr = lax.bitcast_convert_type(thr ^ jnp.uint32(1 << 31), jnp.int32)
    info = jnp.pad(jnp.stack([thr, cut.astype(jnp.int32), pos], axis=-1),
                   ((0, 0), (0, 0), (0, 5)))
    stat_lanes = LANES if keys % LANES == 0 else 1
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(g, h // hg),
        in_specs=[
            pl.BlockSpec((hg, 1, r, width), lambda t, j, *_: (j, t, 0, 0)),
            pl.BlockSpec((1, r, 8), lambda t, j, *_: (t, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((hg, 1, r, rank),
                               lambda t, j, *_: (j, t, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, nblk, block_size, width), pool.dtype),
            pltpu.VMEM((2, r, keys), jnp.uint32),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.VMEM((hg, r, rank), F32),
            pltpu.VMEM((hg, r, stat_lanes), F32),
            pltpu.VMEM((hg, r, stat_lanes), F32),
            pltpu.SMEM((1,), jnp.int32),
        ],
    )
    kernel = functools.partial(
        _sparse_tile_read_kernel, block_size=block_size, scale=scale,
        rank=rank)
    # what the call holds: the double-buffered q and o blocks of a group,
    # the step's blocks and keys twice, accumulator and statistics, and the
    # group's score tiles (about three live): 23 MB at the published widths,
    # past the compiler's default scoped limit
    size = pool.dtype.itemsize
    need = (2 * hg * r * (size * width + q_cat.dtype.itemsize * rank)
            + 2 * keys * (size * width + 4 * r)
            + 4 * hg * r * (rank + 2 * stat_lanes)
            + 3 * 4 * hg * r * keys)
    limit = {"compiler_params": pltpu.CompilerParams(
        vmem_limit_bytes=need + _PREFILL_VMEM_HEADROOM)} \
        if need > _PREFILL_VMEM_BUDGET else {}
    out = pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((h, g, r, rank), q_cat.dtype),
        interpret=bool(interpret),
        **kernel_names(kernel), **limit,
    )(tables, jnp.max(pos, axis=1),
      q_cat.astype(pool.dtype).transpose(2, 0, 1, 3), info, key,
      pool.reshape(nb, block_size, width))
    return out.transpose(1, 2, 0, 3)


# --------------------------------------------------------------------- #
# dslint contract-checker registration (see analysis/pallas_lint.py)
# --------------------------------------------------------------------- #
from deepspeed_tpu.analysis.registry import pallas_kernel_case  # noqa: E402


@pallas_kernel_case(
    "sparse_tile_read",
    vmem_limit=48 << 20,
    note="the tile rows' masked read at the published GLM-5 widths (64 "
         "heads against one 640-lane row, rank 512): pool and keys stay in "
         "HBM (memory_space=ANY); a group of 16 heads' double-buffered "
         "query and output blocks (2 x 2.6 MB, 2 x 2.1 MB), its float32 "
         "accumulator (4.2 MB), the step's two blocks and key slice twice "
         "and the group's score tiles pass the 16 MB default, so the call "
         "asks for its own limit; a pad tile between two sequences' tiles, "
         "the second stops at its own last position")
def _dslint_sparse_tile_read_case():
    import numpy as np

    bs, r, h, width, rank, entries = 128, 128, 64, 640, 512, 12
    rng = np.random.default_rng(5)
    pool = jnp.asarray(
        rng.standard_normal(((2 * entries + 1) * bs, width))
        .astype(np.float32), jnp.bfloat16)
    tables = jnp.asarray(
        np.arange(1, 2 * entries + 1).reshape(2, entries)[[0, 0, 1]],
        jnp.int32)
    last = np.asarray([1400, -1, 300])
    pos = last[:, None] - np.arange(r)[::-1][None]
    pos = jnp.asarray(np.where(last[:, None] >= 0, pos, -1), jnp.int32)
    key = sort_key(jnp.asarray(
        rng.standard_normal((3, r, entries * bs)).astype(np.float32)))
    # (any threshold: the checker runs no kernel body)
    thr, cut = key[:, :, 7], jnp.full((3, r), 40, jnp.int32)
    q = jnp.asarray(
        rng.standard_normal((3, r, h, width)).astype(np.float32) * 0.2,
        jnp.bfloat16)
    sparse_tile_read(q, pool, tables, pos, key, thr, cut, block_size=bs,
                     rank=rank, scale=256 ** -0.5, interpret=True)
