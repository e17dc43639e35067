"""Paged flash attention over the blocked KV pool (reference:
inference/v2/kernels/ragged_ops/blocked_flash/ — flash attention whose KV
comes from paged "atoms" resolved through per-sequence block tables,
``atom_builder`` + ``blocked_flash``).

Pallas TPU kernel using scalar prefetch: the ragged metadata
(``token_slot``, ``token_pos``, ``block_tables``) rides in SMEM and DRIVES
THE BLOCK SPEC INDEX MAPS, so each grid step DMAs exactly the KV pool
block the current token's block table names — no per-token context gather
is ever materialised (the XLA reference path builds a [T, C, Hkv, D]
gather; this kernel's live set is one [block_size, Hkv*D] block plus the
accumulators).

The stored form of a float pool is the one BOTH kernels the serving cells
run read without a copy: ``[rows, Hkv*D]``, a token's heads side by side
in whole 128-lane tiles.  ``[blocks, block_size, Hkv*D]`` is then a free
split of the leading dimension: the tiled kernel's block specs and the
walk's DMAs address it as it lies.  (``[rows, Hkv, D]`` is not that: on the
chip its last two dimensions are tiled, and the flattened-lane view the
tiled kernel wants is a second pool that XLA writes in front of every call.)

Four kernels, by the shape of the rows they serve (the route is
``modules/attention.py::_paged_attention``'s):

* ``_prefill_kernel`` — every ``put`` forward of an engine whose token
  budget is a whole number of tiles, mixed ticks included: the engine packs
  each chunk longer than one token tile-aligned (``RaggedBatchWrapper.
  set_alignment``), so the grid is (tiles, key steps): a step meets a run
  of table entries counted from the tile's first live block, a KV head's
  whole group of query heads in one pair of MXU dots and one online-softmax
  update (the note above the kernel);
* ``_decode_kernel`` — one token a row (a decode step, or the single-token
  rows of such a forward) on a pool the DMA walk can copy
  (``decode_walk_usable``: a float pool in the flat row ``[rows, Hkv*D]``
  of whole lane tiles that ``BlockedKVCache`` stores, heads of whole tiles
  or heads that divide one, the note on its arithmetic below; an int8 pool
  at ``D % 128 == 0``), whatever the pool's size: one grid step per row, a
  manual double-buffered DMA walk over the blocks the row's table holds up
  to its position, the next live row's first blocks in flight while this
  row computes;
* ``_verify_kernel`` — K rows per sequence sharing such a walk (speculation);
* ``_kernel`` — the generic grid (tokens, blocks_per_sequence), one token's
  [H, D] query against one block per step: engines whose budget is no whole
  number of tiles, verify at head sizes the DMA walk cannot copy, and the
  single-token rows on a big pool at such head sizes.

In the grid kernels the block axis is innermost and sequential on TPU, so
fp32 online-softmax accumulators live in VMEM scratch across it (same
structure as ops/flash_attention.py). Invalid table slots (past a
sequence's length) are masked by position — their DMA reads whatever block
the table names (0 for never-written rows), and the mask discards it.  The
two walks copy no block past a row's position at all.
"""

from __future__ import annotations

import functools
import math
from typing import Any

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.inference.v2.ragged.kv_cache import flat_row
from deepspeed_tpu.utils.platform import kernel_names, on_tpu

NEG_INF = -1e30


def _kernel(token_slot, token_pos, tables, q_ref, k_ref, v_ref, o_ref,
            acc_ref, m_ref, l_ref, *, block_size, num_blocks_per_seq,
            scale, window):
    t = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    pos = token_pos[t]
    # skip blocks entirely past this token's position; with a sliding
    # window (Mistral SWA) also skip blocks entirely below pos - window
    run = j * block_size <= pos
    if window is not None:
        run = jnp.logical_and(run,
                              (j + 1) * block_size - 1 > pos - window)

    @pl.when(run)
    def _():
        q = q_ref[0].astype(jnp.float32)              # [H, D]
        k = k_ref[0].astype(jnp.float32)              # [bs, Hkv, D]
        v = v_ref[0].astype(jnp.float32)
        h = q.shape[0]
        hkv = k.shape[1]
        g = h // hkv
        qg = q.reshape(hkv, g, q.shape[1])            # [Hkv, g, D]
        # scores per kv head: [Hkv, g, bs]
        s = jax.lax.dot_general(
            qg, k.transpose(1, 2, 0), (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32) * scale
        key_pos = j * block_size + jax.lax.broadcasted_iota(
            jnp.int32, (hkv, g, block_size), 2)
        keep = key_pos <= pos
        if window is not None:
            keep = jnp.logical_and(keep, key_pos > pos - window)
        s = jnp.where(keep, s, NEG_INF)

        sh = s.reshape(h, block_size)
        m_prev = m_ref[:, :1]
        m_cur = jnp.max(sh, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(sh - m_new)                       # [H, bs]
        corr = jnp.exp(m_prev - m_new)
        l_ref[:] = jnp.broadcast_to(
            l_ref[:, :1] * corr + jnp.sum(p, axis=1, keepdims=True),
            l_ref.shape)
        pg = p.reshape(hkv, g, block_size)
        out = jax.lax.dot_general(
            pg, v.transpose(1, 0, 2), (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)       # [Hkv, g, D]
        acc_ref[:] = acc_ref[:] * corr + out.reshape(h, -1)
        m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)

    @pl.when(j == num_blocks_per_seq - 1)
    def _():
        l = l_ref[:, :1]
        safe_l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_ref[:] / safe_l).astype(o_ref.dtype)


def paged_attention_usable(q, k_pool, block_size: int) -> bool:
    h, d = q.shape[1], q.shape[2]
    hkv = k_pool.shape[1]
    return (h % hkv == 0 and d % 8 == 0 and block_size % 8 == 0)


# ===================================================================== #
# Decode kernel: O(held blocks), manual double-buffered DMA.
#
# The grid-(tokens, blocks) kernel above spends one grid step per
# (token, table entry) — a skinny [H, D] x [bs, Hkv, D] work item whose
# fixed grid-step cost dominates at decode (VERDICT r4 weak #3).  Here
# the KV pool stays in HBM (memory_space=ANY), in the [blocks, bs, Hkv*D]
# view of the flat row it is stored in (a free split of its leading
# dimension: no relayout copy in front of the call), and the kernel runs ONE
# grid step per row: a
# fori_loop with a DYNAMIC trip count walks exactly the blocks the row's
# table holds up to its position — the HBM read volume is the held bytes,
# not O(pool) (the dense XLA read) or O(S * table-width) (grid version).
#
# * One DMA schedule runs through the whole call: a step's last act before
#   it waits for its own blocks is to start the next step's, and the step
#   after a row's last is the NEXT LIVE ROW's first (the cross-sequence
#   prefetch of the public JAX paged-attention TPU kernels), so no row
#   waits for a copy that nothing overlapped but the very first.  Which
#   half of the double buffer the next row starts in rides across grid
#   steps in SMEM.  Pad rows (position -1) start nothing and wait for
#   nothing: a bare grid step.
# * A step is ``_walk_step_blocks`` consecutive table entries (small blocks
#   — few KV heads — are copied several a step, so a step moves about
#   half a megabyte a stream and its fixed cost is paid once); entries
#   past the row's last are neither copied nor waited for, their stale
#   keys are masked by position.
# * The arithmetic of a step (the note below): EVERY KV head in one pair
#   of dots on the pool's dtype over the whole [keys, Hkv*D] step, with
#   float32 accumulation.  No per-head sublane gather, no transpose, no
#   float32 copy of a block.  Softmax statistics stay float32; the
#   probabilities are cast to the pool dtype for PV, as the dense read does.
# ===================================================================== #
# --------------------------------------------------------------------- #
# int8 mode of the decode and verify kernels.  Mosaic takes neither an
# int8 [bs, Hkv, D] tile whose sublane dim Hkv is under the packed int8
# tile, nor a [bs, Hkv] fp32 scale block whose lane dim is Hkv ("Slice
# shape along dimension 2 must be aligned to tiling").  So the payload is
# walked in the flattened-lane view [bs, Hkv*D] (bs rows in sublanes,
# head i in lanes [i*D, (i+1)*D), the prefill kernel's layout) and the
# scales of each sequence's table blocks arrive as [B, Hkv, bs] — rows
# in LANES, which is also the orientation the arithmetic wants: the
# per-row scale multiplies the [g, bs] score tile after the QK dot and
# the probability tile before the PV dot (a lane-wise product on g rows
# instead of a dequantized [bs, D] tile).  Cost to know: at 8 KV heads
# the flattened view is not a free reshape of the [rows, Hkv, D] pool an
# int8 cache still keeps (its scales are a record a KV head) in the TPU
# tiled layout — XLA inserts a relayout copy of the pool in front of the
# call (PERF.md section 7) — so this form is the one that compiles, not yet
# the one that is fast.  No cell serves an int8 pool.
# --------------------------------------------------------------------- #
def _block_scales(scale_pool, block_tables, slots, nb, block_size, hkv):
    """[rows, Hkv] scale pool -> [S, B, Hkv, bs] scales of each
    sequence's table blocks (an XLA gather bounded by the table extent,
    1/32 of the payload bytes it describes at D=128)."""
    return scale_pool.reshape(nb, block_size, hkv)[
        block_tables[slots]].transpose(0, 1, 3, 2)


def _head_tiles(block, hkv, d):
    """[bs, Hkv*D] block -> Hkv fp32 [bs, D] tiles (static, 128-aligned
    lane slices)."""
    return [block[:, i * d:(i + 1) * d].astype(jnp.float32)
            for i in range(hkv)]


def _head_scores(qg, k_tiles, ks, scale):
    """qg [Hkv, g, D], fp32 k tiles, ks [Hkv, bs] the keys' int8 scales
    (None: a float pool) -> [Hkv, g, bs] scaled scores."""
    return jnp.stack([
        jax.lax.dot_general(qg[i], kt, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
        * (scale if ks is None else ks[i:i + 1, :] * scale)
        for i, kt in enumerate(k_tiles)])


def _head_pv(pg, v_tiles, vs):
    """pg [Hkv, g, bs], fp32 v tiles, vs [Hkv, bs] the values' int8 scales
    (None: a float pool) -> [Hkv, g, D]."""
    return jnp.stack([
        jax.lax.dot_general(pg[i] if vs is None else pg[i] * vs[i:i + 1, :],
                            vt, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
        for i, vt in enumerate(v_tiles)])


def _decode_kernel(token_slot, token_pos, tables, q_ref, k_hbm, v_hbm,
                   *refs, block_size, scale, window, quantized=False):
    # a float pool: the note below on the arithmetic over [bs, Hkv*D] blocks.
    # quantized mode walks the SAME block schedule over the int8 payload
    # and applies the scales inside the online-softmax update (see the
    # int8 note above) — never a separate dequantized pass, and the HBM
    # read is int8 bytes plus the small pre-gathered scale block.
    if quantized:
        ks_ref, vs_ref, o_ref, k_buf, v_buf, sems, half_ref = refs
    else:
        o_ref, k_buf, v_buf, sems, half_ref = refs
    streams = ((k_buf, k_hbm, 0), (v_buf, v_hbm, 1))
    t = pl.program_id(0)
    rows = pl.num_programs(0)
    width = tables.shape[1]
    nblk = k_buf.shape[1]                 # table entries a step

    def span(r):
        """[lo, hi): the table entries row ``r`` reads (empty on a pad)."""
        pos = token_pos[r]
        lo = 0
        if window is not None:
            lo = jnp.maximum(0, (pos - window + 1) // block_size)
        return lo, pos // block_size + 1

    def first_step(r):
        """(slot, lo, hi) of the first live row at or after ``r``; an
        empty span when there is none."""
        r = jax.lax.while_loop(
            lambda r: jnp.logical_and(
                r < rows, token_pos[jnp.minimum(r, rows - 1)] < 0),
            lambda r: r + 1, r)
        live = jnp.minimum(r, rows - 1)
        lo, hi = span(live)
        return token_slot[live], lo, jnp.where(r < rows, hi, 0)

    def copies(slot, j0, hi, half, act):
        """``act`` (start / wait) on the copies of entries [j0, j0 + nblk)
        of ``slot``'s table that lie under ``hi``, into ``half``."""
        for u in range(nblk):
            @pl.when(j0 + u < hi)
            def _():
                blk = tables[slot, jnp.minimum(j0 + u, width - 1)]
                for buf, hbm, which in streams:
                    act(pltpu.make_async_copy(
                        hbm.at[blk], buf.at[half, u], sems.at[half, which]))

    @pl.when(t == 0)
    def _():
        half_ref[0] = 0
        if nblk > 1:
            # entries of a step past the row's last are never copied: the
            # keys that stand there are masked, so they must be finite
            k_buf[...] = jnp.zeros_like(k_buf)
            v_buf[...] = jnp.zeros_like(v_buf)
        copies(*first_step(0), 0, lambda c: c.start())

    pos = token_pos[t]
    slot = token_slot[t]
    lo, hi = span(t)
    steps = (hi - lo + nblk - 1) // nblk  # 0 on a pad row
    half0 = half_ref[0]
    after = first_step(t + 1)             # what follows this row's last step

    def advance(i):
        """Start the copies of the step after ``i`` (this row's next, or
        the next live row's first), wait for step ``i``'s own; its first
        table entry and its half of the buffer."""
        j0 = lo + i * nblk
        half = jax.lax.rem(half0 + i, 2)
        last = i + 1 == steps
        copies(jnp.where(last, after[0], slot),
               jnp.where(last, after[1], j0 + nblk),
               jnp.where(last, after[2], hi), 1 - half, lambda c: c.start())
        copies(slot, j0, hi, half, lambda c: c.wait())
        return j0, half

    def visible(key, j0):
        keep = key <= pos - j0 * block_size
        if window is not None:
            keep = jnp.logical_and(keep,
                                   key > pos - window - j0 * block_size)
        return keep

    if not quantized:
        # heads under a lane tile arrive packed to tiles, [1, tiles, ...]
        compute = _packed_walk if q_ref.ndim == 4 else _row_walk
        compute(q_ref, o_ref, k_buf, v_buf, advance, visible, steps, scale)
        half_ref[0] = jax.lax.rem(half0 + steps, 2)
        return

    h, d = q_ref.shape[1:]
    hkv = k_buf.shape[3] // d
    g = h // hkv
    qg = q_ref[0].astype(jnp.float32).reshape(hkv, g, d)
    # every score column is one key of the step's block
    key = jax.lax.broadcasted_iota(jnp.int32, (h, block_size), 1)

    def body(i, carry):
        m_prev, l_prev, acc = carry
        j0, half = advance(i)
        s = _head_scores(qg, _head_tiles(k_buf[half, 0], hkv, d),
                         ks_ref[0, j0], scale).reshape(h, block_size)
        s = jnp.where(visible(key, j0), s, NEG_INF)
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)            # every row sees a key each step
        corr = jnp.exp(m_prev - m_new)
        l_new = l_prev * corr + jnp.sum(p, axis=1, keepdims=True)
        out = _head_pv(p.reshape(hkv, g, block_size),
                       _head_tiles(v_buf[half, 0], hkv, d),
                       vs_ref[0, j0]).reshape(h, d)
        return m_new, l_new, acc * corr + out

    m0 = jnp.full((h, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((h, 1), jnp.float32)
    acc0 = jnp.zeros((h, d), jnp.float32)
    _m, l, acc = jax.lax.fori_loop(0, steps, body, (m0, l0, acc0))
    half_ref[0] = jax.lax.rem(half0 + steps, 2)
    safe_l = jnp.where(l == 0.0, 1.0, l)
    o_ref[0] = (acc / safe_l).astype(o_ref.dtype)


# --------------------------------------------------------------------- #
# The arithmetic of the decode walk on flat [bs, Hkv*D] blocks.
#
# A float pool is stored FLAT, [rows, Hkv*D] in whole 128-lane tiles
# (``BlockedKVCache``; 8 x 128 = 1024 lanes, 8 x 64 = 512), and walked in
# that form: a table block is the contiguous [bs, Hkv*D] it is, so a held
# token moves Hkv*D*2 B a stream and nothing more.  A per-head [keys, D]
# slice of it is cheap (static, whole lane tiles), but a dot a KV head is
# Hkv skinny dots of a few weight tiles each a step, and their latencies,
# not their work, then bound the walk (measured: 27-40% over the read's
# time at 8 and 16 KV heads, PERF.md section 6, PR 41).  So a step is ONE
# pair of dots over every lane of the block, the queries laid out so that a
# head meets its own KV head's lanes alone:
#
# * heads of whole tiles (D = 128, Qwen3-Next's 256; ``_row_walk``): the
#   kernel spreads ``q [H, D]`` block-diagonally over the row's lanes, head
#   ``h`` in the lanes of KV head ``h // g`` and zeros in the rest, once a
#   row.  ``q_bd [H, Hkv*D]`` against the step's ``[keys, Hkv*D]`` is every
#   head's scores ``[H, keys]``: the other heads' lanes meet zeros.  The MXU
#   loads the ``bs*Hkv*D / 128^2`` weight tiles a step holds once, at M = H
#   rows (group size 1, 16 KV heads, needs no path of its own), and softmax
#   runs on ``[H, keys]``.  ``p [H, keys]`` against the value block gives
#   ``[H, Hkv*D]``, accumulated whole; a head's own D lanes are picked out
#   of it once a row.  No product is masked away but by position.
# * heads under a tile (D = 64; ``_packed_walk``): the same idea a 128-lane
#   tile at a time, ``pack = 128 // D`` KV heads side by side.  The wrapper
#   hands in, per lane tile, the ``pack * g`` query heads of its KV heads,
#   each ZERO-PADDED to 128 lanes with its values in its own KV head's lanes
#   (``_pack_queries``), so one dot of [pack*g, 128] with the tile's [keys,
#   128] gives every head's scores against its own KV head alone.  PV is one
#   dot with the value tile, [pack*g, 128], of which a head's own D lanes
#   are kept (``_unpack_heads``).  Cost: ``pack`` times the MXU passes the
#   mathematics needs (a pass is half zeros), on a read bound by its bytes.
#
# Either way: no lane shuffle, no per-head sublane gather, no relayout in
# VMEM, and the bytes moved are the held tokens'.
# --------------------------------------------------------------------- #
def _row_walk(q_ref, o_ref, k_buf, v_buf, advance, visible, steps, scale):
    """The compute of ``_decode_kernel`` on flat blocks at heads of whole
    lane tiles: one online softmax over the step's keys for every head, the
    queries block-diagonal over the row's lanes.  What is done once a row
    (spreading the queries, picking each head's own lanes out of the
    accumulator) is done a [H, D] lane block at a time and not at all on a
    pad row: at a few blocks a row it is what the row costs."""
    h, d = q_ref.shape[1:]
    nblk, block_size, lanes = k_buf.shape[1:]
    keys = nblk * block_size
    hkv = lanes // d
    g = h // hkv
    row = jax.lax.broadcasted_iota(jnp.int32, (h, d), 0)

    def own(i):
        """[H, D] of KV head ``i``'s lanes: the rows of its query heads."""
        return jnp.logical_and(row >= i * g, row < (i + 1) * g)

    @pl.when(steps == 0)
    def _():
        o_ref[0] = jnp.zeros(o_ref.shape[1:], o_ref.dtype)

    @pl.when(steps > 0)
    def _():
        q32 = q_ref[0].astype(jnp.float32)
        q = jnp.concatenate([jnp.where(own(i), q32, 0.0) for i in range(hkv)],
                            axis=1).astype(k_buf.dtype)       # [H, lanes]
        key = jax.lax.broadcasted_iota(jnp.int32, (h, keys), 1)

        def body(i, carry):
            m_prev, l_prev, acc = carry
            j0, half = advance(i)
            s = jax.lax.dot_general(
                q, k_buf.at[half].reshape(keys, lanes)[...],
                (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale   # [H, keys]
            s = jnp.where(visible(key, j0), s, NEG_INF)
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            p = jnp.exp(s - m_new)        # every row sees a key each step
            corr = jnp.exp(m_prev - m_new)
            out = jax.lax.dot_general(
                p.astype(v_buf.dtype),
                v_buf.at[half].reshape(keys, lanes)[...],
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)           # [H, lanes]
            return (m_new,
                    l_prev * corr + jnp.sum(p, axis=1, keepdims=True),
                    acc * corr + out)

        _m, l, acc = jax.lax.fori_loop(0, steps, body, (
            jnp.full((h, 1), NEG_INF, jnp.float32),
            jnp.zeros((h, 1), jnp.float32),
            jnp.zeros((h, lanes), jnp.float32)))
        out = sum(jnp.where(own(i), acc[:, i * d:(i + 1) * d], 0.0)
                  for i in range(hkv))                        # own lanes
        o_ref[0] = (out / jnp.where(l == 0.0, 1.0, l)).astype(o_ref.dtype)


def _packed_walk(q_ref, o_ref, k_buf, v_buf, advance, visible, steps, scale):
    """The compute of ``_decode_kernel`` on flat blocks at heads under a
    lane tile: per lane tile its own online softmax over the step's keys."""
    tiles, heads = q_ref.shape[1:3]       # lane tiles a row, heads a tile
    nblk, block_size, lanes = k_buf.shape[1:]
    keys = nblk * block_size
    key = jax.lax.broadcasted_iota(jnp.int32, (heads, keys), 1)

    def body(i, carry):
        j0, half = advance(i)
        keep = visible(key, j0)
        kk = k_buf.at[half].reshape(keys, lanes)
        vv = v_buf.at[half].reshape(keys, lanes)
        out = []
        for j in range(tiles):
            m_prev, l_prev, acc = carry[3 * j:3 * j + 3]
            lane = slice(j * 128, (j + 1) * 128)
            s = jax.lax.dot_general(
                q_ref[0, j].astype(k_buf.dtype), kk[:, lane],
                (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale  # [heads, keys]
            s = jnp.where(keep, s, NEG_INF)
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            p = jnp.exp(s - m_new)        # every row sees a key each step
            corr = jnp.exp(m_prev - m_new)
            out += [m_new, l_prev * corr + jnp.sum(p, axis=1, keepdims=True),
                    acc * corr + jax.lax.dot_general(
                        p.astype(v_buf.dtype), vv[:, lane],
                        (((1,), (0,)), ((), ())),
                        preferred_element_type=jnp.float32)]  # [heads, 128]
        return tuple(out)

    init = (jnp.full((heads, 1), NEG_INF, jnp.float32),
            jnp.zeros((heads, 1), jnp.float32),
            jnp.zeros((heads, 128), jnp.float32)) * tiles
    final = jax.lax.fori_loop(0, steps, body, init)
    for j in range(tiles):
        l, acc = final[3 * j + 1:3 * j + 3]
        o_ref[0, j] = (acc / jnp.where(l == 0.0, 1.0, l)).astype(o_ref.dtype)


def _pack_queries(q, hkv: int, pack: int):
    """q [T, H, D] -> [T, Hkv/pack, pack*g, pack*D]: the query heads of the
    ``pack`` KV heads of a lane tile, head ``(half, i)`` in row ``half * g +
    i`` with its values in lanes ``[half*D, (half+1)*D)`` and zeros in the
    rest."""
    t, h, d = q.shape
    eye = jnp.eye(pack, dtype=q.dtype).reshape(1, 1, pack, 1, pack, 1)
    return (q.reshape(t, hkv // pack, pack, h // hkv, 1, d) * eye).reshape(
        t, hkv // pack, pack * (h // hkv), pack * d)


def _unpack_heads(o, h: int, pack: int):
    """[T, Hkv/pack, pack*g, pack*D] -> [T, H, D]: each head's own lanes."""
    t, tiles, rows, lanes = o.shape
    o = o.reshape(t, tiles, pack, rows // pack, pack, lanes // pack)
    return jnp.stack([o[:, :, i, :, i] for i in range(pack)],
                     axis=2).reshape(t, h, lanes // pack)


def pool_kv_heads(k_pool, d: int) -> int:
    """KV heads of a pool: the flat row [rows, Hkv*D], or [rows, Hkv, D]."""
    return k_pool.shape[1] // d if k_pool.ndim == 2 else k_pool.shape[1]


def decode_walk_usable(d: int, k_pool) -> bool:
    """Can ``_decode_kernel`` walk this pool?  Blocks whose lanes are whole
    tiles: a float pool in the flat row [rows, Hkv*D] ``BlockedKVCache``
    stores (its rule, ``flat_row``: whole tiles a row, heads of whole tiles
    or heads that divide one; ``_row_walk``, ``_packed_walk``); an int8 pool,
    [rows, Hkv, D] beside its scales, at heads of whole tiles."""
    if k_pool.dtype == jnp.int8:
        return k_pool.ndim == 3 and d % 128 == 0
    return k_pool.ndim == 2 and flat_row(k_pool.dtype, k_pool.shape[1] // d, d)


def _walk_step_blocks(block_bytes: int, width: int, quantized: bool) -> int:
    """Table entries a step of the decode walk copies: as many as bring a
    stream's step to half a megabyte (two at 8 bf16 KV heads of 128, one
    at 16, four at Qwen3-Next's two heads of 256 — measured on a v5e,
    PERF.md section 5), never more than a table holds.  The int8 mode's
    per-head tiles and pre-gathered scales are a block's: one."""
    if quantized:
        return 1
    return max(1, min((512 << 10) // block_bytes, width))


@functools.partial(jax.jit,
                   static_argnames=("block_size", "window", "interpret"))
def paged_decode_attention(q: jnp.ndarray, k_pool: jnp.ndarray,
                           v_pool: jnp.ndarray,
                           block_tables: jnp.ndarray,
                           token_slot: jnp.ndarray,
                           token_pos: jnp.ndarray,
                           *, block_size: int, window: Any = None,
                           interpret: Any = None,
                           k_scale: Any = None,
                           v_scale: Any = None) -> jnp.ndarray:
    """One-token-a-row paged attention: q [T, H, D], row ``t`` the token
    of slot ``token_slot[t]`` at ``token_pos[t]`` (slots in any order, a
    pad row at position -1), KV pool resident in HBM, per-row dynamic walk
    over the blocks its table holds up to that position.  Returns
    [T, H, D] (pad rows give zeros).

    ``k_scale``/``v_scale`` (``[rows, Hkv]`` fp32, int8 pools) switch on
    the fused-dequant mode: the int8 payload is walked block by block
    from HBM, the scales of each sequence's table blocks are gathered
    once (:func:`_block_scales`), and they are applied in VMEM inside the
    online-softmax update."""
    s_count, h, d = q.shape
    quantized = k_scale is not None
    if interpret is None:
        interpret = not on_tpu()
    scale = 1.0 / (d ** 0.5)
    tables = block_tables.astype(jnp.int32)
    slots = token_slot.astype(jnp.int32)
    hkv = pool_kv_heads(k_pool, d)
    if not decode_walk_usable(d, k_pool):
        raise ValueError(
            f"paged_decode_attention: a {k_pool.dtype} pool "
            f"{k_pool.shape} at head size {d} is not one the walk copies: "
            f"a float pool is walked in the flat row [rows, Hkv*D] of "
            f"whole 128-lane tiles it is stored in, an int8 pool as "
            f"[rows, Hkv, D] at D % 128 == 0")
    out_block = (1, h, d)
    pack = 0 if quantized or d % 128 == 0 else 128 // d
    if pack:                              # heads under a lane tile
        q = _pack_queries(q, hkv, pack)
        out_block = (1,) + q.shape[1:]
    nblk = _walk_step_blocks(
        block_size * hkv * d * k_pool.dtype.itemsize,
        tables.shape[1], quantized)
    operands, in_specs, scratch = _walk_operands(
        q, k_pool, v_pool, k_scale, v_scale, tables, slots, block_size,
        (2, nblk))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(s_count,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec(
            out_block,
            lambda t, slot, pos, tab: (t,) + (0,) * (len(out_block) - 1)),
        # which half of the double buffer the next row's first step is in
        scratch_shapes=scratch + [pltpu.SMEM((1,), jnp.int32)],
    )
    kernel = functools.partial(_decode_kernel, block_size=block_size,
                               scale=scale, window=window,
                               quantized=quantized)
    out = pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((s_count,) + out_block[1:], q.dtype),
        interpret=bool(interpret),
        **kernel_names(kernel, op_name=False),
    )(slots, token_pos.astype(jnp.int32), tables, *operands)
    return _unpack_heads(out, h, pack) if pack else out


def _walk_operands(q, k_pool, v_pool, k_scale, v_scale, tables, slots,
                   block_size, lead=(2,)):
    """(operands, in_specs, scratch) shared by the decode and verify
    wrappers: the q block per sequence, the KV pools left in HBM for the
    manual block walk, the double-buffered block scratch (``lead`` blocks
    of it: two halves, times the entries of a step in the decode walk) and
    its DMA semaphores.  Both walk the flat [bs, Hkv*D] blocks a float
    pool is stored in; an int8 pool, [rows, Hkv, D], in that view of it (a
    copy of the pool on the chip, the int8 note) plus each sequence's
    gathered scale blocks."""
    nb = k_pool.shape[0] // block_size
    quantized = k_scale is not None
    block = (block_size, math.prod(k_pool.shape[1:]))
    operands = [q, k_pool.reshape(nb, *block), v_pool.reshape(nb, *block)]
    in_specs = [
        pl.BlockSpec((1,) + q.shape[1:],
                     lambda t, slot, pos, tab: (t,) + (0,) * (q.ndim - 1)),
        pl.BlockSpec(memory_space=pl.ANY),
        pl.BlockSpec(memory_space=pl.ANY),
    ]
    if quantized:
        hkv = k_scale.shape[1]
        in_specs += [pl.BlockSpec((1, tables.shape[1], hkv, block_size),
                                  lambda t, slot, pos, tab: (t, 0, 0, 0))] * 2
        operands += [_block_scales(sc, tables, slots, nb, block_size, hkv)
                     for sc in (k_scale, v_scale)]
    scratch = [pltpu.VMEM(lead + block, k_pool.dtype),
               pltpu.VMEM(lead + block, v_pool.dtype),
               pltpu.SemaphoreType.DMA((2, 2))]
    return operands, in_specs, scratch


# ===================================================================== #
# Multi-token VERIFY kernel (speculative decoding): the decode kernel's
# O(live-context) manual-DMA walk, but with K query rows per sequence —
# the fed token plus K-1 drafted lookahead tokens at consecutive
# positions.  One weight pass scores all K candidate positions: the HBM
# block DMAs are shared across the K rows (the whole point — K tokens
# per Σ live-context read instead of K separate walks), and each row k
# carries its own causal frontier ``pos0 + k``.  This is what lets a
# bandwidth-bound 7B decode emit >1 token per weight stream, and what
# amortises the per-step dispatch cost that dominates 125M decode.
# ===================================================================== #
def _verify_kernel(token_slot, token_pos, tables, q_ref, k_hbm, v_hbm,
                   *refs, block_size, scale, window, k_tokens,
                   quantized=False):
    # same int8 contract as _decode_kernel, and the K query rows share
    # ONE converted block per walk step (the whole point — the int8 read
    # amortises across all K candidate positions)
    if quantized:
        ks_ref, vs_ref, o_ref, k_buf, v_buf, sems = refs
    else:
        o_ref, k_buf, v_buf, sems = refs
    streams = ((k_buf, k_hbm, 0), (v_buf, v_hbm, 1))
    t = pl.program_id(0)
    pos0 = token_pos[t]                   # first fed position (0 on pads)
    slot = token_slot[t]
    last = pos0 + k_tokens - 1            # deepest causal frontier
    hi = last // block_size + 1
    lo = 0
    if window is not None:
        lo = jnp.maximum(0, (pos0 - window + 1) // block_size)
    n = hi - lo

    qf = q_ref[0].astype(jnp.float32)     # [K*H, D], row k*H+h
    h = qf.shape[0] // k_tokens
    d = qf.shape[1]
    hkv = k_buf.shape[2] // d             # flat [bs, Hkv*D] blocks
    g = h // hkv

    def dma(buf, hbm, sl, j, which):
        return pltpu.make_async_copy(
            hbm.at[tables[slot, j]], buf.at[sl], sems.at[sl, which])

    @pl.when(n > 0)
    def _():
        for buf, hbm, which in streams:
            dma(buf, hbm, 0, lo, which).start()

    def body(i, carry):
        m_prev, l_prev, acc = carry       # [K*H,1], [K*H,1], [K*H,D]
        j = lo + i
        sl = jax.lax.rem(i, 2)

        @pl.when(i + 1 < n)
        def _():
            nsl = jax.lax.rem(i + 1, 2)
            for buf, hbm, which in streams:
                dma(buf, hbm, nsl, j + 1, which).start()

        for buf, hbm, which in streams:
            dma(buf, hbm, sl, j, which).wait()
        k_tiles = _head_tiles(k_buf[sl], hkv, d)      # Hkv x [bs, D]
        v_tiles = _head_tiles(v_buf[sl], hkv, d)
        ks = vs = None
        if quantized:
            ks, vs = ks_ref[0, j], vs_ref[0, j]       # [Hkv, bs]
        ms, ls, accs = [], [], []
        for kq in range(k_tokens):        # static unroll: K is small
            q = qf[kq * h:(kq + 1) * h]               # [H, D]
            qg = q.reshape(hkv, g, d)
            s = _head_scores(qg, k_tiles, ks, scale)  # [Hkv, g, bs]
            key_pos = j * block_size + jax.lax.broadcasted_iota(
                jnp.int32, (hkv, g, block_size), 2)
            keep = key_pos <= pos0 + kq   # row k's own causal frontier
            if window is not None:
                keep = jnp.logical_and(keep, key_pos > pos0 + kq - window)
            s = jnp.where(keep, s, NEG_INF)
            sh = s.reshape(h, block_size)
            mp = m_prev[kq * h:(kq + 1) * h]
            m_cur = jnp.max(sh, axis=1, keepdims=True)
            m_new = jnp.maximum(mp, m_cur)
            p = jnp.exp(sh - m_new)                   # [H, bs]
            corr = jnp.exp(mp - m_new)
            ls.append(l_prev[kq * h:(kq + 1) * h] * corr
                      + jnp.sum(p, axis=1, keepdims=True))
            pg = p.reshape(hkv, g, block_size)
            out = _head_pv(pg, v_tiles, vs)           # [Hkv, g, D]
            accs.append(acc[kq * h:(kq + 1) * h] * corr
                        + out.reshape(h, d))
            ms.append(m_new)
        return (jnp.concatenate(ms, axis=0), jnp.concatenate(ls, axis=0),
                jnp.concatenate(accs, axis=0))

    kh = k_tokens * h
    m0 = jnp.full((kh, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((kh, 1), jnp.float32)
    acc0 = jnp.zeros((kh, d), jnp.float32)
    _m, l, acc = jax.lax.fori_loop(0, n, body, (m0, l0, acc0))
    safe_l = jnp.where(l == 0.0, 1.0, l)
    o_ref[0] = (acc / safe_l).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("block_size", "k_tokens", "window",
                                    "interpret"))
def paged_verify_attention(q: jnp.ndarray, k_pool: jnp.ndarray,
                           v_pool: jnp.ndarray,
                           block_tables: jnp.ndarray,
                           token_slot: jnp.ndarray,
                           token_pos: jnp.ndarray,
                           *, block_size: int, k_tokens: int,
                           window: Any = None,
                           interpret: Any = None,
                           k_scale: Any = None,
                           v_scale: Any = None) -> jnp.ndarray:
    """Multi-query paged attention for speculative verify batches.

    q: [T, H, D] with ``T = S * k_tokens`` and rows slot-major — row
    ``s * k_tokens + k`` is slot ``s``'s k-th lookahead token, at
    position ``token_pos[s * k_tokens] + k``.  token_slot/token_pos are
    the row-level [T] arrays the generic kernels take (each slot's K
    rows share a slot id and carry consecutive positions).  Returns
    [T, H, D]; pad slots give garbage-but-finite rows.  The pool in the
    flat row [rows, Hkv*D] it is stored in (an int8 pool [rows, Hkv, D]):
    the walk copies [bs, Hkv*D] blocks and takes a KV head as a static
    lane slice of one, so ``D % 128 == 0``.
    """
    t_count, h, d = q.shape
    s_count = t_count // k_tokens
    quantized = k_scale is not None
    if interpret is None:
        interpret = not on_tpu()

    scale = 1.0 / (d ** 0.5)
    tables = block_tables.astype(jnp.int32)
    # per-slot metadata: the first row of each K-group drives the walk
    slot0 = token_slot.reshape(s_count, k_tokens)[:, 0].astype(jnp.int32)
    pos0 = token_pos.reshape(s_count, k_tokens)[:, 0].astype(jnp.int32)
    operands, in_specs, scratch = _walk_operands(
        q.reshape(s_count, k_tokens * h, d), k_pool, v_pool, k_scale,
        v_scale, tables, slot0, block_size)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(s_count,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, k_tokens * h, d),
                               lambda t, slot, pos, tab: (t, 0, 0)),
        scratch_shapes=scratch,
    )
    kernel = functools.partial(_verify_kernel, block_size=block_size,
                               scale=scale, window=window,
                               k_tokens=k_tokens, quantized=quantized)
    out = pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((s_count, k_tokens * h, d),
                                       q.dtype),
        interpret=bool(interpret),
        **kernel_names(kernel, op_name=False),
    )(slot0, pos0, tables, *operands)
    return out.reshape(t_count, h, d)


# ===================================================================== #
# Tiled prefill (reference ragged_ops/atom_builder + blocked_flash: work
# units are "atoms" = a q-tile of consecutive same-sequence tokens x a KV
# block range). The engine packs every chunk longer than one token
# TILE-ALIGNED in the tiled segment of the token buffer (single-token
# chunks — the decodes of a mixed tick — sit in their own rows in front of
# it and never reach this kernel), so every [tile_q]-row stripe belongs to
# one sequence (pad rows carry position -1 and mask to zero).
#
# The grid is (tiles, key steps).  A key step is a RUN of ``kb`` consecutive
# table entries (``_prefill_step_blocks``; the blocks lie anywhere in the
# pool, so a step has ``kb`` block specs a stream, each with its own index
# map), counted from the tile's FIRST LIVE block, which rides in SMEM beside
# the tile's other scalars: block 0 on a layer that sees every key, the
# block of the lowest key inside the band on a window layer.  The key axis is
# therefore as long as a tile's band can be, not as the table: on a window
# layer ``ceil(band / kb)`` steps with ``band = ceil((window + tile_q) /
# block_size) + 1`` blocks (``_prefill_extent``) whatever ``max_context``
# is; without a window ``ceil(entries / kb)``, and the steps past a tile's
# last block name the blocks of its last live step again, so the pipeline
# moves nothing for them.
#
# A live step takes the KV heads one after the other (a ``fori_loop`` over the
# row's lane tiles: the body is compiled once, not once a head).  The query
# heads of a KV head are stacked to ``[g * tile_q, D]`` once a tile, so a step
# reads that head's ``[kb * bs, D]`` keys and values ONCE for its whole group
# (the MXU streams ``g * tile_q`` rows a weight tile, not ``tile_q``) and
# makes ONE online-softmax update (row max, correction, ``l``, ``m``, the
# accumulator's rescale) for all ``kb * bs`` keys.  That update crosses the
# lanes once a row, for the maximum; everything else of it is lane-wise (the
# note on ``w`` in the kernel): crossed for the sum, the correction and the
# rescale too, the cross-lane unit and neither the MXU nor the VPU bounds the
# step (measured: 3.5 us a (KV head, step) whatever ``kb``, against 1.5).  The
# mask is built only on steps that hold an edge of the band (the causal
# diagonal, the window's lower edge, a pad row, a table entry past the tile's
# last); the steps inside it run the bare update.  Statistics and accumulator
# are float32, the dots take the pool's dtype into float32, the scale
# multiplies the float32 scores (on ``q`` it would cost the bfloat16 queries
# half their precision for 2-4% of the kernel), ``p`` is cast to the pool's
# dtype for PV.
# ===================================================================== #
#: scratch and blocks of the tiled kernel the default scoped limit holds
#: with room for the compiler's own temporaries, and what is added to a
#: larger need when the limit is raised to it
_PREFILL_VMEM_BUDGET = 14 << 20
_PREFILL_VMEM_HEADROOM = 8 << 20
#: keys a step of the tiled kernel meets where the shapes allow it
_PREFILL_STEP_KEYS = 512
#: bytes of ONE float32 score tile ``[g * tile_q, keys]`` a step may hold
#: (the compiler keeps about three of that size: scores, probabilities and
#: their cast)
_PREFILL_SCORE_BYTES = 2 << 20


def _prefill_extent(entries: int, window: Any, tile_q: int,
                    block_size: int) -> int:
    """Table entries one tile's rows can see: the table's, or on a window
    layer the band's, the ``window + tile_q - 1`` keys wherever they start
    in a block."""
    if window is None:
        return entries
    return min(entries, -(-(window + tile_q) // block_size) + 1)


def _prefill_step_blocks(group: int, block_size: int, entries: int,
                         window: Any, tile_q: int) -> int:
    """Table entries a key step of the tiled kernel meets (``kb``), from the
    shapes alone: ``_PREFILL_STEP_KEYS`` keys a step, fewer where one
    float32 score tile of a KV head's whole group ``[group * tile_q, keys]``
    would pass ``_PREFILL_SCORE_BYTES``, never more than the table (or a
    window layer's band) holds.  Four at every serving cell's shapes (block
    128, groups of 1 to 8, tile 128): a window layer's band of 34 blocks is
    nine steps, the last with two entries past it.  Measured on a v5e at
    Trinity's window layer (48 / 8 heads of 128; PERF.md section 6, PR 44),
    microseconds a call by ``kb``: 2: 1,058, 3: 1,377, 4: **869**, 6: 1,002,
    8: 986, 12: 920, 16: 1,168 (a step's fixed work no longer sets the time,
    a longer step only puts more entries past the band's end)."""
    keys = min(_PREFILL_STEP_KEYS,
               _PREFILL_SCORE_BYTES // (4 * group * tile_q))
    return max(1, min(keys // block_size,
                      _prefill_extent(entries, window, tile_q, block_size)))


def _prefill_key_steps(entries: int, window: Any, tile_q: int,
                       block_size: int, kb: int) -> int:
    """The static length of the tiled kernel's key axis."""
    return -(-_prefill_extent(entries, window, tile_q, block_size) // kb)


def prefill_key_steps(chunks, tiles: int, *, group: int, block_size: int,
                      entries: int, window: Any, tile_q: int) -> tuple:
    """(key steps the grid of ONE ``paged_prefill_attention`` call runs,
    those of them that hold a visible key) for a tiled segment of ``tiles``
    tiles that holds ``chunks`` ((start, tokens) each, tile-aligned): host
    arithmetic on the same rule the call takes its step from, for the
    ``engine/build_batch`` span."""
    kb = _prefill_step_blocks(group, block_size, entries, window, tile_q)
    live = 0
    for start, tokens in chunks:
        for lo in range(start, start + tokens, tile_q):
            maxpos = min(lo + tile_q, start + tokens) - 1
            last = maxpos // block_size
            first, seen = 0, 0
            if window is not None:
                first = min(max(
                    (maxpos - tile_q - window + 1) // block_size, 0), last)
                seen = max(lo - window + 1, 0) // block_size
            live += (last - first) // kb - (seen - first) // kb + 1
    return tiles * _prefill_key_steps(entries, window, tile_q, block_size,
                                      kb), live


def _prefill_kernel(tile_slot, tile_first, tile_steps, tile_minpos,
                    tile_maxpos, tables, q_ref, pos_ref, *refs, block_size,
                    scale, tile_q, num_heads, num_kv_heads, window, kb):
    k_refs, v_refs = refs[:kb], refs[kb:2 * kb]
    o_ref, qs_ref, acc_ref, m_ref, l_ref = refs[2 * kb:]
    t = pl.program_id(0)
    j = pl.program_id(1)
    g = num_heads // num_kv_heads
    d = q_ref.shape[1] // num_heads
    rows, keys = g * tile_q, kb * block_size
    # the KV heads a loop step takes: one of whole lane tiles, the heads
    # side by side in one tile (D = 64: two), or, on a row that is no whole
    # tiles (the CPU's small shapes), every head of the row
    lanes = num_kv_heads * d
    if d % 128 == 0:
        unit = d
    elif lanes % 128 == 0 and 128 % d == 0:
        unit = 128
    else:
        unit = lanes
    pack = unit // d

    @pl.when(j == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        # the query heads of a KV head stacked on the rows
        for h in range(num_heads):
            qs_ref[h // g, (h % g) * tile_q:(h % g + 1) * tile_q, :] = \
                q_ref[:, h * d:(h + 1) * d]

    k0 = (tile_first[t] + j * kb) * block_size        # the step's first key
    live = j < tile_steps[t]
    # every row of the tile sees every key of the step: no pad row (their
    # position is -1), the step's last key at or under the lowest position,
    # its first inside the highest position's window
    inside = k0 + keys - 1 <= tile_minpos[t]
    if window is not None:
        inside = jnp.logical_and(inside, k0 > tile_maxpos[t] - window)

    # the statistics of a row, ``w`` lanes wide.  128 (a step's keys are whole
    # lane tiles): ``m`` stands in every lane and ``l`` is kept as 128 partial
    # sums, a lane each, so a step folds its keys' lane tiles into one with
    # plain vector operations and crosses the lanes ONCE, for the row's
    # maximum (the cross-lane unit, not the MXU or the VPU, bounds a step
    # that crosses them for the sum, the correction and the rescale too:
    # PERF.md section 6, PR 44); ``l`` is summed over its lanes once a tile.
    # 1 (the CPU's small blocks): the plain column.
    w = m_ref.shape[-1]

    def fold(x, op, over_row):
        """[rows, keys] -> [rows, w]: ``op`` over the keys' lane tiles (w
        1: ``over_row``, the same reduction over the whole row)."""
        if w == 1:
            return over_row(x, axis=1, keepdims=True)
        return functools.reduce(
            op, [x[:, c * w:(c + 1) * w] for c in range(keys // w)])

    def spread(x, n):
        """[rows, w] -> [rows, n]: the row's value in every lane."""
        if w == 1 or n == w:
            return x
        return x[:, :n] if n < w else jnp.tile(x, (1, n // w))

    def step(masked):
        if masked:
            pos = jnp.concatenate([pos_ref[:, :1]] * g, axis=0)   # [rows, 1]
            key_pos = k0 + jax.lax.broadcasted_iota(
                jnp.int32, (rows, keys), 1)
            keep = key_pos <= pos
            if window is not None:
                keep = jnp.logical_and(keep, key_pos > pos - window)

        def heads(i):
            lane = slice(None) if unit == lanes else pl.ds(
                pl.multiple_of(i * unit, unit), unit)
            ku = jnp.concatenate([r[0, :, lane] for r in k_refs], axis=0)
            vu = jnp.concatenate([r[0, :, lane] for r in v_refs], axis=0)
            for r in range(pack):
                head = i * pack + r
                kh = ku[:, r * d:(r + 1) * d] if pack > 1 else ku
                vh = vu[:, r * d:(r + 1) * d] if pack > 1 else vu
                s = jax.lax.dot_general(
                    qs_ref[head], kh, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32) * scale
                if masked:
                    s = jnp.where(keep, s, NEG_INF)
                m_prev = m_ref[head]                          # [rows, w]
                m_new = jnp.maximum(m_prev, jnp.max(
                    fold(s, jnp.maximum, jnp.max), axis=1, keepdims=True))
                p = jnp.exp(s - spread(m_new, keys))
                if masked:
                    p = jnp.where(keep, p, 0.0)  # all-masked rows: exp(0)
                corr = jnp.exp(m_prev - m_new)
                l_ref[head] = l_ref[head] * corr + fold(p, jnp.add, jnp.sum)
                acc_ref[head] = acc_ref[head] * spread(corr, d) \
                    + jax.lax.dot_general(
                        p.astype(vh.dtype), vh, (((1,), (0,)), ((), ())),
                        preferred_element_type=jnp.float32)
                m_ref[head] = m_new

        if unit == lanes:
            heads(0)
        else:
            jax.lax.fori_loop(0, lanes // unit,
                              lambda i, c: (heads(i), c)[1], 0)

    pl.when(jnp.logical_and(live, inside))(lambda: step(False))
    pl.when(jnp.logical_and(live, jnp.logical_not(inside)))(
        lambda: step(True))

    @pl.when(j == pl.num_programs(1) - 1)
    def _():
        for h in range(num_heads):
            own = slice((h % g) * tile_q, (h % g + 1) * tile_q)
            l = jnp.sum(l_ref[h // g, own, :], axis=1, keepdims=True)
            o_ref[:, h * d:(h + 1) * d] = (
                acc_ref[h // g, own, :]
                / jnp.where(l == 0.0, 1.0, l)).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("block_size", "tile_q", "window",
                                    "interpret"))
def paged_prefill_attention(q: jnp.ndarray, k_pool: jnp.ndarray,
                            v_pool: jnp.ndarray, block_tables: jnp.ndarray,
                            token_slot: jnp.ndarray,
                            token_pos: jnp.ndarray,
                            *, block_size: int, tile_q: int,
                            window: Any = None,
                            interpret: Any = None) -> jnp.ndarray:
    """Tiled paged attention for TILE-ALIGNED token buffers.

    q: [T, H, D] with every [tile_q] stripe single-sequence; token_pos
    [T] int32 with -1 on pad rows. Returns [T, H, D] (pad rows 0).
    The pool in the flat row [rows, Hkv*D] it is stored in: the kernel's
    [blocks, bs, Hkv*D] is then a free split of the leading dimension (a
    [rows, Hkv, D] pool is taken too, through a copy of it on the chip).

    The grid is (tiles, key steps): a step meets ``kb`` consecutive table
    entries (``_prefill_step_blocks``) counted from the tile's first live
    block, so a window layer runs the steps its band needs and no more,
    whatever the table's width (the note above ``_prefill_kernel``).
    """
    t_count, h, d = q.shape
    hkv = pool_kv_heads(k_pool, d)
    nb = k_pool.shape[0] // block_size
    s_count, b_per_seq = block_tables.shape
    nt = t_count // tile_q
    if interpret is None:
        interpret = not on_tpu()

    # flattened-lane layouts (see _prefill_kernel): q/o [T, H*D], pools
    # [nb, bs, Hkv*D] (the stored row, its leading dimension split)
    qf = q.reshape(t_count, h * d)
    kp = k_pool.reshape(nb, block_size, hkv * d)
    vp = v_pool.reshape(nb, block_size, hkv * d)
    scale = 1.0 / (d ** 0.5)
    kb = _prefill_step_blocks(h // hkv, block_size, b_per_seq, window,
                              tile_q)
    steps = _prefill_key_steps(b_per_seq, window, tile_q, block_size, kb)

    # per-tile metadata (XLA-land, cheap): the stripe's slot, its highest
    # and lowest position (-1: a pad row), the first and last table entry
    # its rows see and the key steps between them (none on a tile of pads)
    pos = token_pos.reshape(nt, tile_q).astype(jnp.int32)
    tile_slot = token_slot.reshape(nt, tile_q)[:, 0].astype(jnp.int32)
    tile_maxpos, tile_minpos = pos.max(axis=1), pos.min(axis=1)
    last = jnp.maximum(tile_maxpos, 0) // block_size
    tile_first = jnp.zeros_like(last)
    if window is not None:
        tile_first = jnp.clip(
            (tile_maxpos - tile_q - window + 1) // block_size, 0, last)
    tile_steps = jnp.where(tile_maxpos >= 0, (last - tile_first) // kb + 1,
                           0)
    pos8 = jnp.broadcast_to(token_pos.astype(jnp.int32)[:, None],
                            (t_count, 8))

    # lanes of a row's softmax statistics (the note in the kernel)
    stat_lanes = 128 if (kb * block_size) % 128 == 0 and (
        d <= 128 or d % 128 == 0) else 1

    def kv_spec(u):
        def index(t, j, slot, first, n, lo, hi, tab):
            # a step past the tile's last live one names that one's blocks
            # again; an entry past the tile's last block names that block
            # (its keys are masked by position)
            jj = jnp.minimum(j, jnp.maximum(n[t] - 1, 0))
            return (tab[slot[t], jnp.minimum(
                first[t] + jj * kb + u,
                jnp.maximum(hi[t], 0) // block_size)], 0, 0)
        return pl.BlockSpec((1, block_size, hkv * d), index)

    tile = pl.BlockSpec((tile_q, h * d), lambda t, j, *_: (t, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=6,
        grid=(nt, steps),
        in_specs=[tile, pl.BlockSpec((tile_q, 8), lambda t, j, *_: (t, 0))]
        + [kv_spec(u) for u in range(kb)] * 2,
        out_specs=tile,
        scratch_shapes=[
            pltpu.VMEM((hkv, h // hkv * tile_q, d), q.dtype),
            pltpu.VMEM((hkv, h // hkv * tile_q, d), jnp.float32),
            pltpu.VMEM((hkv, h // hkv * tile_q, stat_lanes), jnp.float32),
            pltpu.VMEM((hkv, h // hkv * tile_q, stat_lanes), jnp.float32),
        ],
    )
    kernel = functools.partial(
        _prefill_kernel, block_size=block_size, scale=scale, tile_q=tile_q,
        num_heads=h, num_kv_heads=hkv, window=window, kb=kb)
    # what the call holds: the double-buffered q / o blocks and the kb k / v
    # blocks a stream, the stacked queries, the float32 accumulator and
    # statistics of every head, and a KV head's score tiles (about three of
    # them live at once).  13 MB at LFM2's 32 heads of 64, inside the
    # compiler's default scoped limit (16 MiB); 19 MB at Mistral's 32 heads
    # of 128 and 26 MB at Trinity's 48 bring the limit they need
    size = q.dtype.itemsize
    need = (2 * 2 * size * tile_q * h * d
            + 2 * 2 * size * kb * block_size * hkv * d
            + h * tile_q * (d * (size + 4) + 2 * 128 * 4)
            + 3 * 4 * (h // hkv) * tile_q * kb * block_size)
    limit = {"compiler_params": pltpu.CompilerParams(
        vmem_limit_bytes=need + _PREFILL_VMEM_HEADROOM)} \
        if need > _PREFILL_VMEM_BUDGET else {}
    out = pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((t_count, h * d), q.dtype),
        interpret=bool(interpret),
        **kernel_names(kernel, op_name=False), **limit,
    )(tile_slot, tile_first, tile_steps, tile_minpos, tile_maxpos,
      block_tables.astype(jnp.int32), qf, pos8, *[kp] * kb, *[vp] * kb)
    return out.reshape(t_count, h, d)


@functools.partial(jax.jit,
                   static_argnames=("block_size", "window", "interpret"))
def paged_attention(q: jnp.ndarray, k_pool: jnp.ndarray,
                    v_pool: jnp.ndarray, block_tables: jnp.ndarray,
                    token_slot: jnp.ndarray, token_pos: jnp.ndarray,
                    *, block_size: int, window: Any = None,
                    interpret: Any = None) -> jnp.ndarray:
    """q: [T, H, D]; k/v_pool: [num_blocks*block_size, Hkv, D];
    block_tables: [S, B] int32; token_slot/token_pos: [T] int32.
    Returns [T, H, D] — each token attends over its sequence's paged
    context up to its own position; ``window`` (Mistral SWA) restricts it
    to the last ``window`` positions, with out-of-band pool blocks skipped
    entirely (the DMA index map clamps into the live band, so skipped
    iterations re-name an already-resident block and the pipeline elides
    the transfer).
    """
    t_count, h, d = q.shape
    hkv = k_pool.shape[1]
    nb = k_pool.shape[0] // block_size
    s_count, b_per_seq = block_tables.shape
    if interpret is None:
        interpret = not on_tpu()

    kp = k_pool.reshape(nb, block_size, hkv, d)
    vp = v_pool.reshape(nb, block_size, hkv, d)
    scale = 1.0 / (d ** 0.5)

    def _kv_index(t, j, slot, pos, tab):
        # clamp out-of-band block indices into the token's live band:
        # skipped iterations then revisit an already-resident pool block,
        # which the Pallas pipeline elides instead of DMAing garbage
        # (pad rows of a two-segment batch carry position -1: block 0)
        last = jnp.maximum(pos[t], 0) // block_size
        jj = jnp.minimum(j, last)
        if window is not None:
            lo = jnp.maximum((pos[t] - window + 1) // block_size, 0)
            jj = jnp.maximum(jj, jnp.minimum(lo, last))
        return (tab[slot[t], jj], 0, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(t_count, b_per_seq),
        in_specs=[
            pl.BlockSpec((1, h, d),
                         lambda t, j, slot, pos, tab: (t, 0, 0)),
            pl.BlockSpec((1, block_size, hkv, d), _kv_index),
            pl.BlockSpec((1, block_size, hkv, d), _kv_index),
        ],
        out_specs=pl.BlockSpec((1, h, d),
                               lambda t, j, slot, pos, tab: (t, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((h, d), jnp.float32),
            pltpu.VMEM((h, 128), jnp.float32),
            pltpu.VMEM((h, 128), jnp.float32),
        ],
    )
    kernel = functools.partial(_kernel, block_size=block_size,
                               num_blocks_per_seq=b_per_seq, scale=scale,
                               window=window)
    return pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((t_count, h, d), q.dtype),
        interpret=bool(interpret),
        **kernel_names(kernel, op_name=False),
    )(token_slot.astype(jnp.int32), token_pos.astype(jnp.int32),
      block_tables.astype(jnp.int32), q, kp, vp)


# --------------------------------------------------------------------- #
# dslint contract-checker registration (see analysis/pallas_lint.py):
# the selftest paged geometry — scalar-prefetched block tables drive
# the index maps, so the bounds check runs with the REAL table values.
# --------------------------------------------------------------------- #
from deepspeed_tpu.analysis.registry import pallas_kernel_case  # noqa: E402


def _flat(pool):
    """[rows, Hkv, D] -> the flat row [rows, Hkv*D] a float pool is stored
    in."""
    return pool.reshape(pool.shape[0], -1)


def _dslint_paged_setup(d: int):
    import numpy as np

    bs, S, B = 128, 4, 4
    rng = np.random.default_rng(5)
    pool = lambda: jnp.asarray(
        rng.standard_normal(((S * B + 1) * bs, 2, d)).astype(np.float32),
        jnp.bfloat16)
    tables = jnp.arange(1, S * B + 1, dtype=jnp.int32).reshape(S, B)
    token_pos = jnp.asarray([200, 317, 64, 450], jnp.int32)
    token_slot = jnp.arange(S, dtype=jnp.int32)
    q = jnp.asarray(rng.standard_normal((S, 8, d)).astype(np.float32),
                    jnp.bfloat16)
    return bs, pool(), pool(), tables, token_slot, token_pos, q


@pallas_kernel_case("paged_attention_grid",
                    note="grid-(tokens, blocks) paged attention")
def _dslint_paged_grid_case():
    bs, kp, vp, tables, slot, pos, q = _dslint_paged_setup(64)
    paged_attention(q, kp, vp, tables, slot, pos, block_size=bs,
                    interpret=True)


@pallas_kernel_case(
    "paged_decode_dma",
    note="decode walk over the blocks each row holds: KV pool stays in HBM "
         "(memory_space=ANY blocks are exempt from the VMEM estimate; "
         "the double-buffered step scratch is what counts)")
def _dslint_paged_decode_dma_case():
    bs, kp, vp, tables, slot, pos, q = _dslint_paged_setup(128)
    paged_decode_attention(q, _flat(kp), _flat(vp), tables, slot, pos,
                           block_size=bs, interpret=True)


@pallas_kernel_case(
    "paged_verify_multiquery",
    note="speculative multi-token verify: K=4 query rows per sequence "
         "share the decode kernel's O(live-context) block walk (KV pool "
         "in HBM via memory_space=ANY; the double-buffered block "
         "scratch is the VMEM cost)")
def _dslint_paged_verify_case():
    import numpy as np

    K = 4
    bs, kp, vp, tables, slot, pos, _q = _dslint_paged_setup(128)
    S = tables.shape[0]
    rng = np.random.default_rng(7)
    qv = jnp.asarray(rng.standard_normal((S * K, 8, 128)).astype(np.float32),
                     jnp.bfloat16)
    vslot = jnp.repeat(jnp.arange(S, dtype=jnp.int32), K)
    vpos = (pos[:, None] + jnp.arange(K, dtype=jnp.int32)[None, :]).reshape(-1)
    paged_verify_attention(qv, kp, vp, tables, vslot, vpos,
                           block_size=bs, k_tokens=K, interpret=True)


def _dslint_paged_int8_setup():
    import numpy as np

    bs, kp, vp, tables, slot, pos, _q = _dslint_paged_setup(128)
    rows = kp.shape[0]
    rng = np.random.default_rng(11)
    kq = jnp.asarray(rng.integers(-127, 128, size=(rows, 2, 128)), jnp.int8)
    vq = jnp.asarray(rng.integers(-127, 128, size=(rows, 2, 128)), jnp.int8)
    ks = jnp.asarray(rng.random((rows, 2), np.float32) * 0.05)
    vs = jnp.asarray(rng.random((rows, 2), np.float32) * 0.05)
    return bs, kq, vq, ks, vs, tables, slot, pos


@pallas_kernel_case(
    "paged_decode_dma_int8",
    note="int8 block-quantized decode: the payload walks in HBM "
         "(memory_space=ANY) as flattened-lane [bs, Hkv*D] blocks and "
         "the scales are applied around the dots inside the walk — the "
         "VMEM cost is the int8 block scratch plus each sequence's "
         "pre-gathered [B, Hkv, bs] scale blocks")
def _dslint_paged_decode_int8_case():
    import numpy as np

    bs, kq, vq, ks, vs, tables, slot, pos = _dslint_paged_int8_setup()
    S = tables.shape[0]
    rng = np.random.default_rng(12)
    q = jnp.asarray(rng.standard_normal((S, 8, 128)).astype(np.float32),
                    jnp.bfloat16)
    paged_decode_attention(q, kq, vq, tables, slot, pos, block_size=bs,
                           k_scale=ks, v_scale=vs, interpret=True)


@pallas_kernel_case(
    "paged_verify_multiquery_int8",
    note="int8 speculative verify: K=4 query rows share one "
         "fused-dequant block walk (the int8 payload DMA and its "
         "conversion amortise across every candidate position)")
def _dslint_paged_verify_int8_case():
    import numpy as np

    K = 4
    bs, kq, vq, ks, vs, tables, slot, pos = _dslint_paged_int8_setup()
    S = tables.shape[0]
    rng = np.random.default_rng(13)
    qv = jnp.asarray(rng.standard_normal((S * K, 8, 128)).astype(np.float32),
                     jnp.bfloat16)
    vslot = jnp.repeat(jnp.arange(S, dtype=jnp.int32), K)
    vpos = (pos[:, None] + jnp.arange(K, dtype=jnp.int32)[None, :]).reshape(-1)
    paged_verify_attention(qv, kq, vq, tables, vslot, vpos,
                           block_size=bs, k_tokens=K,
                           k_scale=ks, v_scale=vs, interpret=True)


@pallas_kernel_case("paged_prefill",
                    note="tile-aligned prefill at the shipped 125M "
                         "serving geometry (6q/2kv heads, d=64): a key step "
                         "of the table's four entries, a block spec an entry "
                         "and stream")
def _dslint_paged_prefill_case():
    import numpy as np

    bs, kp, vp, tables, _slot, _pos, _q = _dslint_paged_setup(64)
    T = 256
    rng = np.random.default_rng(6)
    qp = jnp.asarray(rng.standard_normal((T, 6, 64)).astype(np.float32),
                     jnp.bfloat16)
    paged_prefill_attention(qp, kp, vp, tables,
                            jnp.zeros((T,), jnp.int32),
                            jnp.arange(T, dtype=jnp.int32),
                            block_size=bs, tile_q=128, interpret=True)


@pallas_kernel_case(
    "paged_decode_dma_d64",
    note="decode walk over flat [bs, Hkv*D] blocks at D = 64 (8q/2kv): "
         "one lane tile a block, ragged positions")
def _dslint_paged_decode_dma_d64_case():
    bs, kp, vp, tables, slot, pos, q = _dslint_paged_setup(64)
    paged_decode_attention(q, _flat(kp), _flat(vp), tables, slot, pos,
                           block_size=bs, interpret=True)
