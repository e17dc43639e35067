"""Learned sparse attention over a paged pool of latent rows (DeepSeek Sparse
Attention, ``model_type: glm_moe_dsa``): beside the latent row ``[c | k_pe |
0]`` of ``latent_flash.py`` every token keeps a second, narrow row, its
**indexer key** (``index_head_dim`` values = one 128-lane tile), behind the
same block table.  A query row first scores EVERY cached position of its
sequence against that narrow row,

    I[t, s] = sum_j w[t, j] * relu(qI[t, j] . kI[s])        (float32),

takes the exact top ``index_topk`` of them (ties to the lowest position) and
attends over those latent rows alone.  Three steps, each an XLA composition
that walks a row's block table in steps of :data:`KEY_BLOCK` positions up to
the furthest position any row of the call holds (a ``while`` loop: nothing
past it is read, and nothing of the pool but through a table):

* :func:`index_scores`: the walk over the narrow leaf, 256 B a position;
  what lies past a row's own position scores ``-inf``.
* the selection, exact, two ways.  **Rows that share a context** (the
  tiles of a prompt chunk): :func:`select_threshold`, a radix select on the
  scores' bits, finds each row's ``k``-th largest score (16 passes over the
  scores, two bits each) and, where several positions tie on it, the
  position up to which the ties are taken (8 passes); the set is
  then ``score > t or (score == t and position <= cut)``, which the read
  applies as a mask, block by block (:func:`masked_latent_read`: all of a
  tile's rows against the same latent block in ONE product, absorbed).  A
  sort of a chunk's 1,024 x 33 k scores would cost several times the
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

import jax
import jax.numpy as jnp
from jax import lax

F32 = jnp.float32
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
