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
  ``v_head_dim`` over that buffer (``_latent_prefill_kernel``, the structure
  of ``blocked_flash._prefill_kernel``).

Both prefill kernels run a grid (tiles, blocks of a table) and skip the steps
past what a chunk or a tile holds; the block index of a skipped step is the
last one that did work (``_forward_fill``), so the pipeline neither fetches
nor writes back anything for it.

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
    NEG_INF, _walk_step_blocks)
from deepspeed_tpu.utils.platform import kernel_names, on_tpu

LANES = 128


def latent_row_width(kv_lora_rank: int, rope_dim: int) -> int:
    """Lanes of a cached row: latent and rotated key, up to whole tiles."""
    return -(-(kv_lora_rank + rope_dim) // LANES) * LANES


def latent_kernels_usable(kv_lora_rank: int, nope: int, v_dim: int,
                          block_size: int) -> bool:
    """Can the Mosaic kernels serve this geometry?  (Otherwise the model
    takes its XLA compositions, as it does off the TPU.)"""
    return (kv_lora_rank % LANES == 0 and nope % LANES == 0
            and v_dim % LANES == 0 and block_size % 16 == 0)


# ===================================================================== #
# Absorbed: the decode walk over one stream
# ===================================================================== #
def _latent_decode_kernel(token_slot, token_pos, tables, q_ref, pool_hbm,
                          o_ref, buf, sems, half_ref, *, block_size, scale,
                          value_dim):
    t = pl.program_id(0)
    rows = pl.num_programs(0)
    width = tables.shape[1]
    nblk = buf.shape[1]                   # table entries a step

    def span(r):
        return token_pos[r] // block_size + 1     # 0 on a pad row (-1)

    def first_step(r):
        """(slot, hi) of the first live row at or after ``r``; hi = 0 when
        there is none."""
        r = jax.lax.while_loop(
            lambda r: jnp.logical_and(
                r < rows, token_pos[jnp.minimum(r, rows - 1)] < 0),
            lambda r: r + 1, r)
        live = jnp.minimum(r, rows - 1)
        return token_slot[live], jnp.where(r < rows, span(live), 0)

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
        slot0, hi0 = first_step(0)
        copies(slot0, 0, hi0, 0, lambda c: c.start())

    pos = token_pos[t]
    slot = token_slot[t]
    hi = span(t)
    steps = (hi + nblk - 1) // nblk       # 0 on a pad row
    half0 = half_ref[0]
    after = first_step(t + 1)

    h = q_ref.shape[1]
    q = q_ref[0].astype(buf.dtype)        # [H, W]
    cols = nblk * block_size
    key = jax.lax.broadcasted_iota(jnp.int32, (h, cols), 1)

    def body(i, carry):
        m_prev, l_prev, acc = carry
        j0 = i * nblk
        half = jax.lax.rem(half0 + i, 2)
        last = i + 1 == steps
        copies(jnp.where(last, after[0], slot),
               jnp.where(last, 0, j0 + nblk),
               jnp.where(last, after[1], hi), 1 - half, lambda c: c.start())
        copies(slot, j0, hi, half, lambda c: c.wait())
        block = buf.at[half].reshape(cols, buf.shape[-1])[...]
        s = jax.lax.dot_general(
            q, block, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale        # [H, cols]
        s = jnp.where(key <= pos - j0 * block_size, s, NEG_INF)
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
                                             "scale", "interpret"))
def latent_decode_attention(q: jnp.ndarray, pool: jnp.ndarray,
                            block_tables: jnp.ndarray,
                            token_slot: jnp.ndarray, token_pos: jnp.ndarray,
                            *, block_size: int, value_dim: int, scale: float,
                            interpret: Any = None) -> jnp.ndarray:
    """One-token-a-row absorbed attention: q [T, H, W] (``q_lat | q_pe |
    0``, as wide as a pool row), row ``t`` the token of slot
    ``token_slot[t]`` at ``token_pos[t]`` (slots in any order, a pad row at
    position -1); pool [rows, W].  Returns ``sum p c`` [T, H, value_dim]
    (pad rows give zeros); the caller applies ``W_uv``."""
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
                               scale=scale, value_dim=value_dim)
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


def _latent_prefill_kernel(kv_blk, tile_maxpos, q_ref, pos_ref, kv_ref,
                           o_ref, acc_ref, m_ref, l_ref, *, block_size,
                           blocks_per_seq, scale, tile_q, num_heads, nope,
                           v_dim):
    del kv_blk
    t = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    @pl.when(j * block_size <= tile_maxpos[t])        # -1 on a pad tile
    def _():
        pos = pos_ref[:, :1]                          # [tile_q, 1] (-1 pads)
        key_pos = j * block_size + jax.lax.broadcasted_iota(
            jnp.int32, (tile_q, block_size), 1)
        keep = key_pos <= pos
        per = nope + v_dim                            # a head's lanes
        qw = q_ref.shape[1] // num_heads              # nope | rope, padded
        kpe = kv_ref[0, :, num_heads * per:]          # [bs, 128]
        for h in range(num_heads):
            qn = q_ref[:, h * qw:h * qw + nope]
            qp = q_ref[:, h * qw + nope:(h + 1) * qw]
            kn = kv_ref[0, :, h * per:h * per + nope]
            vb = kv_ref[0, :, h * per + nope:(h + 1) * per]
            dims = (((1,), (1,)), ((), ()))
            s = (jax.lax.dot_general(qn, kn, dims,
                                     preferred_element_type=jnp.float32)
                 + jax.lax.dot_general(qp, kpe, dims,
                                       preferred_element_type=jnp.float32)
                 ) * scale
            s = jnp.where(keep, s, NEG_INF)
            m_prev = m_ref[h, :, :1]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            p = jnp.where(keep, jnp.exp(s - m_new), 0.0)
            corr = jnp.exp(m_prev - m_new)
            l_ref[h] = jnp.broadcast_to(
                l_ref[h, :, :1] * corr + jnp.sum(p, axis=1, keepdims=True),
                l_ref[h].shape)
            acc_ref[h] = acc_ref[h] * corr + jax.lax.dot_general(
                p.astype(vb.dtype), vb, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_ref[h] = jnp.broadcast_to(m_new, m_ref[h].shape)

    @pl.when(j == blocks_per_seq - 1)
    def _():
        for h in range(num_heads):
            l = l_ref[h, :, :1]
            safe_l = jnp.where(l == 0.0, 1.0, l)
            o_ref[:, h * v_dim:(h + 1) * v_dim] = (
                acc_ref[h] / safe_l).astype(o_ref.dtype)


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
    [T, H, v_dim] (pad rows 0)."""
    t_count, h, qw = q.shape
    if interpret is None:
        interpret = not on_tpu()
    _slot, maxpos, owner, _blocks = plan
    nt = t_count // tile_q
    b_per_seq = expanded.shape[0] // nt
    j = jnp.arange(b_per_seq, dtype=jnp.int32)
    valid = (j[None, :] * block_size <= maxpos[:, None]).reshape(-1)
    kv_blk = _forward_fill(
        (owner[:, None] * b_per_seq + j[None, :]).reshape(-1), valid)
    pos8 = jnp.broadcast_to(token_pos.astype(jnp.int32)[:, None],
                            (t_count, 8))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(nt, b_per_seq),
        in_specs=[
            pl.BlockSpec((tile_q, h * qw), lambda t, j, *_: (t, 0)),
            pl.BlockSpec((tile_q, 8), lambda t, j, *_: (t, 0)),
            pl.BlockSpec((1, block_size, expanded.shape[2]),
                         lambda t, j, blk, maxpos:
                         (blk[t * b_per_seq + j], 0, 0)),
        ],
        out_specs=pl.BlockSpec((tile_q, h * v_dim),
                               lambda t, j, *_: (t, 0)),
        scratch_shapes=[
            pltpu.VMEM((h, tile_q, v_dim), jnp.float32),
            pltpu.VMEM((h, tile_q, LANES), jnp.float32),
            pltpu.VMEM((h, tile_q, LANES), jnp.float32),
        ],
    )
    kernel = functools.partial(
        _latent_prefill_kernel, block_size=block_size,
        blocks_per_seq=b_per_seq, scale=scale, tile_q=tile_q, num_heads=h,
        nope=nope, v_dim=v_dim)
    out = pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((t_count, h * v_dim), q.dtype),
        interpret=bool(interpret),
        **kernel_names(kernel),
    )(kv_blk, maxpos, q.reshape(t_count, h * qw), pos8, expanded)
    return out.reshape(t_count, h, v_dim)


# --------------------------------------------------------------------- #
# dslint contract-checker registration (see analysis/pallas_lint.py)
# --------------------------------------------------------------------- #
from deepspeed_tpu.analysis.registry import pallas_kernel_case  # noqa: E402


def _dslint_latent_setup():
    import numpy as np

    bs, s_count, b, h, rank, rope, nope, vd = 128, 4, 4, 16, 512, 64, 128, 128
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
         "flash read at key width 192 / value width 128")
def _dslint_latent_expand_prefill_case():
    import numpy as np

    bs, h, rank, rope, nope, vd, _w, pool, w_kvb, tables, rng = \
        _dslint_latent_setup()
    tile, t_rows = 128, 3 * 128
    slot = np.zeros((t_rows,), np.int32)
    pos = np.full((t_rows,), -1, np.int32)
    slot[:200], pos[:200] = 1, np.arange(100, 300)       # two tiles
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
