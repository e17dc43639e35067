"""Attention over a paged pool of LATENT rows (multi-head latent attention,
``model_type: deepseek_v3``): per token and layer the pool keeps ONE row
``[c (kv_lora_rank) | k_pe (rope dims) | 0]`` shared by every head, padded to
whole 128-lane tiles (512 + 64 -> 640), instead of per-head keys and values.
The same algebra is computed two ways over that pool:

* **absorbed** (one-token rows): ``W_uk`` is folded into the query and
  ``W_uv`` applied to the output, so a head's query is as wide as the row and
  the value is the row's first ``kv_lora_rank`` lanes.  ``_latent_decode_kernel``
  is the decode walk of ``blocked_flash._decode_kernel`` over ONE stream: per
  live row a double-buffered DMA walk over the blocks its table holds up to
  its position, all heads against the one row in one dot, and ``p @ c`` read
  from the same VMEM copy, so a held token's row leaves HBM once.
* **expanded** (prompt chunks, packed as whole tiles): the chunk's context
  rows are expanded to per-head keys and values once per chunk
  (``_latent_expand_kernel``: the rows gathered through the block table,
  times ``W_kvb``, into a scratch buffer indexed by the chunk's first tile),
  then causal flash attention at key width nope + rope and value width
  ``v_head_dim`` over that buffer (``_latent_prefill_kernel``: a grid of
  (tiles, key steps of 512 keys: ONE block of the chunk's contiguous
  buffer), the heads in a loop compiled once a group of eight, one
  online-softmax update a (head, step) with lane-wise statistics, the mask
  and the zeroing of never-written values on edge steps only; the note
  above the kernel has the arithmetic and the measured ceiling).

The expansion runs a grid (tiles, blocks of a table), the read (tiles, key
steps); both skip the steps past what a chunk or a tile holds.  The block
index of a skipped step of the expansion is the last one that did work
(``_forward_fill``), that of the read the one the next live step needs
(``_next_fill``): the pipeline neither fetches nor writes back anything
more for it.  ``latent_expand`` never writes the blocks past a chunk's last
position; the read keeps what stands there out of its result itself.

Per head, ``W_kvb``'s columns are ``k_nope (qk_nope_head_dim) | v
(v_head_dim)``, the published ``kv_b_proj`` layout; the kernels here need
both to be whole lane tiles (128 in every published configuration).
"""

from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.inference.v2.kernels.blocked_flash import (
    _PREFILL_VMEM_BUDGET, _PREFILL_VMEM_HEADROOM, NEG_INF,
    _prefill_step_blocks, _walk_step_blocks)
from deepspeed_tpu.utils.platform import kernel_names, on_tpu

LANES = 128


def latent_row_width(kv_lora_rank: int, rope_dim: int) -> int:
    """Lanes of a cached row: latent and rotated key, up to whole tiles."""
    return -(-(kv_lora_rank + rope_dim) // LANES) * LANES


def latent_walk_usable(kv_lora_rank: int, block_size: int) -> bool:
    """Can the absorbed walk serve this row?  It meets the row as it lies
    (every head against the whole padded row, the value its first
    ``kv_lora_rank`` lanes), so the row's width decides and a head's
    ``nope`` does not (192 + 64 at rank 1,024 walks)."""
    return kv_lora_rank % LANES == 0 and block_size % 16 == 0


def latent_kernels_usable(kv_lora_rank: int, nope: int, v_dim: int,
                          block_size: int) -> bool:
    """Can the Mosaic kernels serve this geometry, the expanded pair
    included (they slice a head's ``k_nope | v`` and ``q_nope | q_pe`` at
    lane tiles)?  (Otherwise the model takes its XLA compositions, as it
    does off the TPU.)"""
    return (latent_walk_usable(kv_lora_rank, block_size)
            and nope % LANES == 0 and v_dim % LANES == 0)


# ===================================================================== #
# Absorbed: the decode walk over one stream
# ===================================================================== #
def _latent_decode_kernel(token_slot, token_pos, tables, q_ref, pool_hbm,
                          o_ref, buf, sems, half_ref, *, block_size, scale,
                          value_dim, window=None):
    t = pl.program_id(0)
    rows = pl.num_programs(0)
    width = tables.shape[1]
    nblk = buf.shape[1]                   # table entries a step

    def span(r):
        """[lo, hi): the table entries row ``r`` reads (empty on a pad row,
        position -1); a window layer's start at the block of ``pos - window
        + 1``, the band's first."""
        lo = 0
        if window is not None:
            lo = jnp.maximum(0, (token_pos[r] - window + 1) // block_size)
        return lo, token_pos[r] // block_size + 1

    def first_step(r):
        """(slot, lo, hi) of the first live row at or after ``r``; an empty
        span when there is none."""
        r = jax.lax.while_loop(
            lambda r: jnp.logical_and(
                r < rows, token_pos[jnp.minimum(r, rows - 1)] < 0),
            lambda r: r + 1, r)
        live = jnp.minimum(r, rows - 1)
        lo, hi = span(live)
        return token_slot[live], lo, jnp.where(r < rows, hi, 0)

    def copies(slot, j0, hi, half, act):
        for u in range(nblk):
            @pl.when(j0 + u < hi)
            def _():
                blk = tables[slot, jnp.minimum(j0 + u, width - 1)]
                act(pltpu.make_async_copy(
                    pool_hbm.at[blk], buf.at[half, u], sems.at[half]))

    @pl.when(t == 0)
    def _():
        half_ref[0] = 0
        if nblk > 1:
            # entries of a step past the row's last are never copied: what
            # stands there is masked, so it must be finite
            buf[...] = jnp.zeros_like(buf)
        copies(*first_step(0), 0, lambda c: c.start())

    pos = token_pos[t]
    slot = token_slot[t]
    lo, hi = span(t)
    steps = (hi - lo + nblk - 1) // nblk  # 0 on a pad row
    half0 = half_ref[0]
    after = first_step(t + 1)

    h = q_ref.shape[1]
    q = q_ref[0].astype(buf.dtype)        # [H, W]
    cols = nblk * block_size
    key = jax.lax.broadcasted_iota(jnp.int32, (h, cols), 1)

    def body(i, carry):
        m_prev, l_prev, acc = carry
        j0 = lo + i * nblk
        half = jax.lax.rem(half0 + i, 2)
        last = i + 1 == steps
        copies(jnp.where(last, after[0], slot),
               jnp.where(last, after[1], j0 + nblk),
               jnp.where(last, after[2], hi), 1 - half, lambda c: c.start())
        copies(slot, j0, hi, half, lambda c: c.wait())
        block = buf.at[half].reshape(cols, buf.shape[-1])[...]
        s = jax.lax.dot_general(
            q, block, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale        # [H, cols]
        keep = key <= pos - j0 * block_size
        if window is not None:
            keep = jnp.logical_and(
                keep, key > pos - window - j0 * block_size)
        s = jnp.where(keep, s, NEG_INF)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)            # every row sees a key each step
        corr = jnp.exp(m_prev - m_new)
        l_new = l_prev * corr + jnp.sum(p, axis=1, keepdims=True)
        # the value is the row's latent part: the same VMEM copy
        out = jax.lax.dot_general(
            p.astype(buf.dtype), block[:, :value_dim],
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)                # [H, value]
        return m_new, l_new, acc * corr + out

    m0 = jnp.full((h, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((h, 1), jnp.float32)
    acc0 = jnp.zeros((h, value_dim), jnp.float32)
    _m, l, acc = jax.lax.fori_loop(0, steps, body, (m0, l0, acc0))
    half_ref[0] = jax.lax.rem(half0 + steps, 2)
    safe_l = jnp.where(l == 0.0, 1.0, l)
    o_ref[0] = (acc / safe_l).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_size", "value_dim",
                                             "scale", "window", "interpret"))
def latent_decode_attention(q: jnp.ndarray, pool: jnp.ndarray,
                            block_tables: jnp.ndarray,
                            token_slot: jnp.ndarray, token_pos: jnp.ndarray,
                            *, block_size: int, value_dim: int, scale: float,
                            window: Any = None,
                            interpret: Any = None) -> jnp.ndarray:
    """One-token-a-row absorbed attention: q [T, H, W] (``q_lat | q_pe |
    0``, as wide as a pool row), row ``t`` the token of slot
    ``token_slot[t]`` at ``token_pos[t]`` (slots in any order, a pad row at
    position -1); pool [rows, W].  ``window`` (a window layer's latent
    rows): row ``t`` sees the keys ``j`` with ``pos - window < j <= pos``
    and the walk starts at the block of ``pos - window + 1``, so the table
    entries below it (released, the trash block) are never read.  Returns
    ``sum p c`` [T, H, value_dim] (pad rows give zeros); the caller applies
    ``W_uv``."""
    t_count, h, w = q.shape
    if interpret is None:
        interpret = not on_tpu()
    tables = block_tables.astype(jnp.int32)
    nb = pool.shape[0] // block_size
    nblk = _walk_step_blocks(block_size * w * pool.dtype.itemsize,
                             tables.shape[1], False)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(t_count,),
        in_specs=[pl.BlockSpec((1, h, w), lambda t, *_: (t, 0, 0)),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((1, h, value_dim), lambda t, *_: (t, 0, 0)),
        scratch_shapes=[pltpu.VMEM((2, nblk, block_size, w), pool.dtype),
                        pltpu.SemaphoreType.DMA((2,)),
                        pltpu.SMEM((1,), jnp.int32)],
    )
    kernel = functools.partial(_latent_decode_kernel, block_size=block_size,
                               scale=scale, value_dim=value_dim,
                               window=window)
    return pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((t_count, h, value_dim), q.dtype),
        interpret=bool(interpret),
        **kernel_names(kernel),
    )(token_slot.astype(jnp.int32), token_pos.astype(jnp.int32), tables, q,
      pool.reshape(nb, block_size, w))


# ===================================================================== #
# Expanded: context rows -> per-head keys and values, then tiled flash
# ===================================================================== #
def _forward_fill(idx, valid):
    """``idx`` where ``valid``, else the last valid one before it in grid
    order (0 before the first): consecutive grid steps that name the same
    block move nothing."""
    at = jnp.arange(idx.shape[0], dtype=jnp.int32)
    return idx[jax.lax.cummax(jnp.where(valid, at, 0))]


def _next_fill(idx, valid):
    """``idx`` where ``valid``, else the NEXT valid one after it in grid
    order (after the last: that one).  A run of skipped steps then names the
    block the next live step needs: the pipeline fetches it once, under the
    live step before the run, and moves nothing else."""
    ahead = _forward_fill(idx[::-1], valid[::-1])[::-1]
    return _forward_fill(ahead, jnp.logical_or(
        valid, jnp.cumsum(valid[::-1])[::-1] > 0))


def _latent_expand_kernel(work, src_blk, dst_blk, lat_ref, w_ref, o_ref, *,
                          rank, blocks_per_seq):
    del src_blk, dst_blk                  # read by the index maps
    step = pl.program_id(0) * blocks_per_seq + pl.program_id(1)

    @pl.when(work[step] > 0)
    def _():
        lat = lat_ref[0]                              # [bs, W]
        n = w_ref.shape[1]
        o_ref[0, :, :n] = jax.lax.dot_general(
            lat[:, :rank], w_ref[...], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32).astype(o_ref.dtype)
        o_ref[0, :, n:] = lat[:, rank:]               # k_pe | 0, one tile


# --------------------------------------------------------------------- #
# The expanded read.  The grid is (tiles, key steps).  A chunk's expanded
# context is contiguous (block ``owner * B + j`` of ``latent_expand``'s
# buffer), so a key step is ONE block of ``kb * block_size`` rows of the
# whole ``[k_nope | v] x heads | k_pe`` row (``_latent_step_blocks``: 512 keys
# where the table holds them, four entries at a block of 128: 4.3 MB at the
# published widths), not ``kb`` block specs: the key axis is ``ceil(B / kb)``
# long.  A step past a tile's last live one names the block the NEXT live
# step needs, the next tile's first (``_next_fill``): the pipeline fetches
# that block once either way, but under the tile's last live step and not in
# front of the next tile's first, and moves nothing for the other skipped
# steps (a skipped step costs ~0.4 us; measured on a v5e, us a call of a
# 1,024-token chunk from 2,048 by ``kb``: 1: 1,148, 2: 696, 4: **463**,
# 8: 466 in the first form; PERF.md section 6, PR 45).
#
# A live step takes the heads in a ``fori_loop`` over groups of
# ``_HEAD_GROUP`` with dynamic 128-aligned lane slices (``q_nope | q_pe`` of
# the query tile, ``k_nope | v`` of the step's block, the head's
# accumulator): the body is compiled once a group, and inside it the
# scheduler overlaps one head's dots with another's softmax (a loop a head
# read 22% slower than the heads written out, groups of eight read the
# same).  ``k_pe``, shared by all heads, is read once a step outside the loop.
# Every head has keys and values of its own (this is multi-head attention
# over the expansion: there is no group to stack on the rows as
# ``blocked_flash._prefill_kernel`` does), so the MXU streams ``tile_q`` rows
# a weight tile.  A (head, step) makes ONE online-softmax update for all its
# keys, and that update crosses the lanes once a row, for the maximum: ``m``
# stands in all 128 lanes, ``l`` is 128 lane-wise partial sums (summed once a
# tile at the write-out), the scores' lane tiles are folded with plain vector
# ``maximum`` / ``add`` (``fold`` / ``spread`` in the kernel; with ``[rows,
# 1]`` columns the cross-lane unit sets a step's time: 37% slower here, PR 44
# found it first).  At a step under a lane tile of keys (the CPU's small
# tables) the statistics are the plain column.
#
# The mask is built on EDGE steps only: a step whose last key is at or under
# the tile's lowest position (no pad row: those are -1) runs the bare update.
# An edge step also meets rows that were never written: ``latent_expand``
# writes a chunk's blocks up to its last position, and a step of ``kb``
# entries that holds that block reads up to ``kb - 1`` entries past it (and,
# where ``kb`` does not divide the table, rows past the buffer's end):
# uninitialised memory.  A NaN there leaves the scores by the mask
# (``where``), but ``0 x NaN`` in ``p @ v`` is NaN, so an edge step zeroes the
# values of the keys past the tile's highest position before PV.  Nothing
# outside this kernel initialises the buffer.
#
# Statistics and accumulator are float32, the dots take the buffer's dtype
# into float32 (``q_nope . k_nope`` and ``q_pe . k_pe`` are two dots into one
# score tile; one dot over a concatenated 256-lane key read the same), the
# scale multiplies the float32 scores, ``p`` is cast to the buffer's dtype
# for PV.
#
# The ceiling at a query block of 128 rows: every tile of a chunk reads the
# chunk's whole visible context again at ``heads * (nope + v) + 128`` lanes a
# key (8,448 B at the published widths) for ``heads * 2 * (nope + rope + v)
# * tile_q`` = 1.31 MFLOP of causal work: 155 FLOP a byte against the v5e's
# 240 (197 TFLOP/s over 819 GB/s), so the buffer's stream, not the MXU,
# bounds the call at ~64% of the bf16 peak whatever the step does.  Measured:
# 39% of that peak for the visible pairs at a start of 2,048 and 48% at
# 6,144; a query block of two tiles of one chunk (half the stream, twice the
# rows a weight tile) read 47% and 61% and is not in (PERF.md section 7).
# --------------------------------------------------------------------- #
def _latent_step_blocks(block_size: int, entries: int, tile_q: int) -> int:
    """Table entries a key step of the expanded read meets (``kb``), from
    the shapes alone: the tiled kernel's rule for a group of one
    (``_PREFILL_STEP_KEYS`` keys, never more than the table holds)."""
    return _prefill_step_blocks(1, block_size, entries, None, tile_q)


#: heads a step of the expanded read's head loop takes (the most)
_HEAD_GROUP = 8


def latent_prefill_key_steps(chunks, tiles: int, *, block_size: int,
                             entries: int, tile_q: int) -> tuple:
    """(key steps the grid of ONE ``latent_prefill_attention`` call runs,
    those of them that hold a visible key) for a tile segment of ``tiles``
    tiles that holds ``chunks`` ((start, tokens) each, tile-aligned): host
    arithmetic on the rule the call takes its step from, for the
    ``engine/build_batch`` span."""
    kb = _latent_step_blocks(block_size, entries, tile_q)
    live = sum(
        (min(lo + tile_q, start + tokens) - 1) // (kb * block_size) + 1
        for start, tokens in chunks
        for lo in range(start, start + tokens, tile_q))
    return tiles * -(-entries // kb), live


def _latent_prefill_kernel(kv_own, kv_step, tile_minpos, tile_maxpos, q_ref,
                           pos_ref, kv_ref, o_ref, acc_ref, m_ref, l_ref, *,
                           scale, tile_q, num_heads, group, nope, v_dim):
    del kv_own, kv_step                   # read by the index map
    t = pl.program_id(0)
    j = pl.program_id(1)
    keys = kv_ref.shape[1]
    per = nope + v_dim                    # a head's lanes: k_nope | v
    qw = q_ref.shape[1] // num_heads      # q_nope | q_pe, padded to a tile
    # lanes of a row's statistics: 128 (``m`` in every lane, ``l`` a partial
    # sum a lane) where a step's keys are whole lane tiles, else 1
    w = m_ref.shape[-1]

    @pl.when(j == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    k0 = j * keys                         # the step's first key
    live = k0 <= tile_maxpos[t]           # -1 on a pad tile
    inside = k0 + keys - 1 <= tile_minpos[t]      # -1 with a pad row

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
        dims = (((1,), (1,)), ((), ()))
        kpe = kv_ref[0, :, num_heads * per:]                  # [keys, 128]
        if masked:
            keep = k0 + jax.lax.broadcasted_iota(
                jnp.int32, (tile_q, keys), 1) <= pos_ref[:, :1]
            # keys past the tile's highest position: never written, maybe
            written = k0 + jax.lax.broadcasted_iota(
                jnp.int32, (keys, v_dim), 0) <= tile_maxpos[t]

        def head(h):
            at = pl.multiple_of(h * qw, LANES)
            qn = q_ref[:, pl.ds(at, nope)]
            qp = q_ref[:, pl.ds(pl.multiple_of(at + nope, LANES), qw - nope)]
            at = pl.multiple_of(h * per, LANES)
            kn = kv_ref[0, :, pl.ds(at, nope)]
            vb = kv_ref[0, :, pl.ds(pl.multiple_of(at + nope, LANES), v_dim)]
            s = (jax.lax.dot_general(qn, kn, dims,
                                     preferred_element_type=jnp.float32)
                 + jax.lax.dot_general(qp, kpe, dims,
                                       preferred_element_type=jnp.float32)
                 ) * scale
            if masked:
                s = jnp.where(keep, s, NEG_INF)
                vb = jnp.where(written, vb, jnp.zeros_like(vb))
            m_prev = m_ref[h]                                 # [tile_q, w]
            m_new = jnp.maximum(m_prev, jnp.max(
                fold(s, jnp.maximum, jnp.max), axis=1, keepdims=True))
            p = jnp.exp(s - spread(m_new, keys))
            if masked:
                p = jnp.where(keep, p, 0.0)      # all-masked rows: exp(0)
            corr = jnp.exp(m_prev - m_new)
            l_ref[h] = l_ref[h] * corr + fold(p, jnp.add, jnp.sum)
            acc_ref[h] = acc_ref[h] * spread(corr, v_dim) \
                + jax.lax.dot_general(
                    p.astype(vb.dtype), vb, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
            m_ref[h] = m_new

        # the heads in groups of ``group``: a loop over the groups, compiled
        # once, a group's members at static offsets inside its body
        def heads(i, carry):
            for r in range(group):
                head(i * group + r)
            return carry

        jax.lax.fori_loop(0, num_heads // group, heads, 0)

    pl.when(jnp.logical_and(live, inside))(lambda: step(False))
    pl.when(jnp.logical_and(live, jnp.logical_not(inside)))(
        lambda: step(True))

    @pl.when(j == pl.num_programs(1) - 1)
    def _():
        for h in range(num_heads):
            l = jnp.sum(l_ref[h], axis=1, keepdims=True)
            o_ref[:, h * v_dim:(h + 1) * v_dim] = (
                acc_ref[h] / jnp.where(l == 0.0, 1.0, l)).astype(o_ref.dtype)


def _chunk_plan(token_slot, token_pos, block_size, tile_q):
    """Per tile of a tile segment (every [tile_q] stripe one sequence's,
    pad rows at position -1; a sequence has at most one chunk in a batch):
    its slot, its last position, the first tile of its chunk (where the
    chunk's expanded context lives) and, on such a first tile, how many
    table blocks the chunk's context spans (0 elsewhere)."""
    nt = token_pos.shape[0] // tile_q
    slot = token_slot.reshape(nt, tile_q)[:, 0].astype(jnp.int32)
    maxpos = token_pos.reshape(nt, tile_q).max(axis=1).astype(jnp.int32)
    real = maxpos >= 0
    same = real[:, None] & real[None, :] & (slot[:, None] == slot[None, :])
    owner = jnp.where(real, jnp.argmax(same, axis=1).astype(jnp.int32),
                      jnp.arange(nt, dtype=jnp.int32))
    chunk_max = jnp.max(jnp.where(same, maxpos[None, :], -1), axis=1)
    first = real & (owner == jnp.arange(nt, dtype=jnp.int32))
    blocks = jnp.where(first, chunk_max // block_size + 1, 0)
    return slot, maxpos, owner, blocks


@functools.partial(jax.jit, static_argnames=("block_size", "tile_q", "rank",
                                             "interpret"))
def latent_expand(pool: jnp.ndarray, w_kvb: jnp.ndarray,
                  block_tables: jnp.ndarray, token_slot: jnp.ndarray,
                  token_pos: jnp.ndarray, *, block_size: int, tile_q: int,
                  rank: int, interpret: Any = None):
    """The contexts of a tile segment's chunks, expanded: ``[tiles *
    blocks_per_seq, block_size, N + 128]`` where block ``t * B + j`` holds
    table entry ``j`` of the chunk whose FIRST tile is ``t``: ``c @ W_kvb``
    (``N`` lanes: per head ``k_nope | v``) then ``k_pe | 0``.  Blocks past a
    chunk's last position, and those of tiles that start no chunk, are
    never written (and never read by :func:`latent_prefill_attention`).
    Returns ``(expanded, plan)`` with ``plan`` = :func:`_chunk_plan`."""
    if interpret is None:
        interpret = not on_tpu()
    w = pool.shape[1]
    nb = pool.shape[0] // block_size
    n = w_kvb.shape[1]
    tables = block_tables.astype(jnp.int32)
    b_per_seq = tables.shape[1]
    plan = _chunk_plan(token_slot, token_pos, block_size, tile_q)
    slot, _maxpos, _owner, blocks = plan
    nt = slot.shape[0]
    j = jnp.arange(b_per_seq, dtype=jnp.int32)
    work = (j[None, :] < blocks[:, None]).reshape(-1)
    dst = jnp.arange(nt * b_per_seq, dtype=jnp.int32)
    src = tables[slot].reshape(-1)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(nt, b_per_seq),
        in_specs=[
            pl.BlockSpec((1, block_size, w),
                         lambda t, j, work, src, dst:
                         (src[t * b_per_seq + j], 0, 0)),
            pl.BlockSpec((rank, n), lambda t, j, *_: (0, 0)),
        ],
        out_specs=pl.BlockSpec(
            (1, block_size, n + w - rank),
            lambda t, j, work, src, dst: (dst[t * b_per_seq + j], 0, 0)),
    )
    kernel = functools.partial(_latent_expand_kernel, rank=rank,
                               blocks_per_seq=b_per_seq)
    out = pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(
            (nt * b_per_seq, block_size, n + w - rank), pool.dtype),
        interpret=bool(interpret),
        # W_kvb double-buffered (2 x 4 MB at the published widths) beside
        # the row blocks passes the 16 MB default
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=64 << 20),
        **kernel_names(kernel),
    )(work.astype(jnp.int32), _forward_fill(src, work),
      _forward_fill(dst, work), pool.reshape(nb, block_size, w),
      w_kvb.astype(pool.dtype))
    return out, plan


@functools.partial(jax.jit, static_argnames=("block_size", "tile_q", "nope",
                                             "v_dim", "scale", "interpret"))
def latent_prefill_attention(q: jnp.ndarray, expanded: jnp.ndarray, plan,
                             token_pos: jnp.ndarray, *, block_size: int,
                             tile_q: int, nope: int, v_dim: int,
                             scale: float, interpret: Any = None):
    """Causal flash attention of a tile segment over its chunks' expanded
    contexts.  q [T, H, nope + 128]: per head ``q_nope | q_pe | 0`` (the
    rope part padded to a lane tile, as the cached ``k_pe`` is); returns
    [T, H, v_dim] (pad rows 0).  The grid is (tiles, key steps of
    ``_latent_step_blocks`` table entries): the note above
    ``_latent_prefill_kernel``."""
    t_count, h, qw = q.shape
    if interpret is None:
        interpret = not on_tpu()
    _slot, maxpos, owner, _blocks = plan
    nt = t_count // tile_q
    b_per_seq = expanded.shape[0] // nt
    lanes = expanded.shape[2]
    kb = _latent_step_blocks(block_size, b_per_seq, tile_q)
    keys = kb * block_size
    steps = -(-b_per_seq // kb)
    minpos = token_pos.reshape(nt, tile_q).min(axis=1).astype(jnp.int32)
    # the block of every grid step, as (chunk, step of the chunk): a step
    # past a tile's last live one names the next live step's block
    j = jnp.arange(steps, dtype=jnp.int32)
    valid = (j[None, :] * keys <= maxpos[:, None]).reshape(-1)
    at = _next_fill((owner[:, None] * steps + j[None, :]).reshape(-1), valid)
    pos8 = jnp.broadcast_to(token_pos.astype(jnp.int32)[:, None],
                            (t_count, 8))
    stat_lanes = LANES if keys % LANES == 0 else 1
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(nt, steps),
        in_specs=[
            pl.BlockSpec((tile_q, h * qw), lambda t, j, *_: (t, 0)),
            pl.BlockSpec((tile_q, 8), lambda t, j, *_: (t, 0)),
            pl.BlockSpec((1, keys, lanes),
                         lambda t, j, own, stp, lo, hi:
                         (own[t * steps + j], stp[t * steps + j], 0)),
        ],
        out_specs=pl.BlockSpec((tile_q, h * v_dim),
                               lambda t, j, *_: (t, 0)),
        scratch_shapes=[
            pltpu.VMEM((h, tile_q, v_dim), jnp.float32),
            pltpu.VMEM((h, tile_q, stat_lanes), jnp.float32),
            pltpu.VMEM((h, tile_q, stat_lanes), jnp.float32),
        ],
    )
    kernel = functools.partial(
        _latent_prefill_kernel, scale=scale, tile_q=tile_q, num_heads=h,
        group=max(g for g in range(1, _HEAD_GROUP + 1) if h % g == 0),
        nope=nope, v_dim=v_dim)
    # what the call holds: the double-buffered key block (8.7 MB at the
    # published widths and 512 keys), the double-buffered q / o blocks, the
    # float32 accumulator and statistics of every head, and a head's score
    # tiles (about three live at once): 15.5 MB there, past what the
    # compiler's default scoped limit (16 MiB) holds beside its own
    # temporaries, so the call brings the limit it needs
    size = q.dtype.itemsize
    need = (2 * size * keys * lanes
            + 2 * size * tile_q * h * (qw + v_dim)
            + h * tile_q * (v_dim + 2 * LANES) * 4
            + 3 * 4 * tile_q * keys)
    limit = {"compiler_params": pltpu.CompilerParams(
        vmem_limit_bytes=need + _PREFILL_VMEM_HEADROOM)} \
        if need > _PREFILL_VMEM_BUDGET else {}
    out = pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((t_count, h * v_dim), q.dtype),
        interpret=bool(interpret),
        **kernel_names(kernel), **limit,
    )(at // steps, at % steps, minpos, maxpos, q.reshape(t_count, h * qw),
      pos8, expanded.reshape(nt, b_per_seq * block_size, lanes))
    return out.reshape(t_count, h, v_dim)


# --------------------------------------------------------------------- #
# dslint contract-checker registration (see analysis/pallas_lint.py)
# --------------------------------------------------------------------- #
from deepspeed_tpu.analysis.registry import pallas_kernel_case  # noqa: E402


def _dslint_latent_setup():
    import numpy as np

    bs, s_count, b, h, rank, rope, nope, vd = 128, 4, 6, 16, 512, 64, 128, 128
    w = latent_row_width(rank, rope)
    rng = np.random.default_rng(5)
    pool = jnp.asarray(
        rng.standard_normal(((s_count * b + 1) * bs, w)).astype(np.float32),
        jnp.bfloat16).at[:, rank + rope:].set(0)
    w_kvb = jnp.asarray(
        rng.standard_normal((rank, h * (nope + vd))).astype(np.float32)
        * rank ** -0.5, jnp.bfloat16)
    tables = jnp.arange(1, s_count * b + 1, dtype=jnp.int32).reshape(
        s_count, b)
    return bs, h, rank, rope, nope, vd, w, pool, w_kvb, tables, rng


@pallas_kernel_case(
    "latent_decode_dma",
    note="absorbed latent read at the published Moonlight widths (16 heads "
         "against one 640-lane row): the pool stays in HBM "
         "(memory_space=ANY); the double-buffered three-block step scratch "
         "is the VMEM cost")
def _dslint_latent_decode_case():
    import numpy as np

    bs, h, rank, rope, _n, _v, w, pool, _wk, tables, rng = \
        _dslint_latent_setup()
    q = jnp.asarray(rng.standard_normal((4, h, w)).astype(np.float32) * 0.2,
                    jnp.bfloat16)
    latent_decode_attention(
        q, pool, tables, jnp.asarray([2, 0, 3, 1], jnp.int32),
        jnp.asarray([200, -1, 450, 64], jnp.int32), block_size=bs,
        value_dim=rank, scale=(128 + rope) ** -0.5, interpret=True)


@pallas_kernel_case(
    "latent_expand_prefill",
    vmem_limit=64 << 20,
    allow=("pallas-uncovered-tile",),
    note="a tile segment's chunks through the expansion (W_kvb resident, "
         "double-buffered: 8 MB, hence the raised limit; blocks past a "
         "chunk's context are never written, by contract) and the expanded "
         "flash read at key width 192 / value width 128: a key step is one "
         "[512, 4224] block of a chunk's buffer (four of the table's six "
         "entries; the second step's block ends past the buffer), 8.7 MB "
         "double-buffered, so the call asks for 24 MB of VMEM itself; the "
         "two-tile chunk crosses the step at key 512, its second tile is "
         "inside on the first step")
def _dslint_latent_expand_prefill_case():
    import numpy as np

    bs, h, rank, rope, nope, vd, _w, pool, w_kvb, tables, rng = \
        _dslint_latent_setup()
    tile, t_rows = 128, 3 * 128
    slot = np.zeros((t_rows,), np.int32)
    pos = np.full((t_rows,), -1, np.int32)
    slot[:200], pos[:200] = 1, np.arange(400, 600)       # two tiles
    slot[256:300], pos[256:300] = 3, np.arange(0, 44)
    slot, pos = jnp.asarray(slot), jnp.asarray(pos)
    kv, plan = latent_expand(pool, w_kvb, tables, slot, pos, block_size=bs,
                             tile_q=tile, rank=rank, interpret=True)
    q = jnp.asarray(
        rng.standard_normal((t_rows, h, nope + 128)).astype(np.float32),
        jnp.bfloat16)
    latent_prefill_attention(q, kv, plan, pos, block_size=bs, tile_q=tile,
                             nope=nope, v_dim=vd,
                             scale=(nope + rope) ** -0.5, interpret=True)
