"""Pallas flash attention (role of the reference's fused attention CUDA:
csrc/transformer/inference flash path and inference/v2 blocked_flash
``inference/v2/kernels/ragged_ops/blocked_flash/``).

Blockwise online-softmax attention tiled for the MXU:

* forward: grid ``(batch, heads, q_blocks, k_blocks)`` — the k-block axis is
  innermost and sequential on TPU, so fp32 accumulators (acc, running max m,
  running sum l) live in VMEM scratch across k iterations.
* causal (and sliding-window) calls do a tile's live part only: a tile
  outside the band does no work and fetches nothing, one inside it runs
  with no mask, and one that an edge of the band crosses multiplies,
  exponentiates and masks the sub-blocks the band leaves it ("A causal
  tile's live part" below; the bshd kernels).
* backward: the standard two-kernel flash backward — dQ over k-blocks and
  dK/dV over q-blocks — recomputing probabilities from the saved logsumexp
  instead of storing the [Sq, Sk] matrix.
* GQA: k/v BlockSpec index maps collapse a group of ``H // Hkv`` query heads
  onto their shared KV head; dK/dV are accumulated per q-head and group-summed
  outside the kernel.

Layout: [batch, seq, heads, head_dim] at the boundary (matching
``ops.attention``), transposed to [B, H, S, D] around the kernels.
``interpret=True`` (automatic off-TPU) runs the same kernels through the
Pallas interpreter so CPU tests exercise identical code.

``flash_attention_folded`` is the family for heads narrower than the
128-lane tile (``ops.attention`` picks it from the head size): q/k/v stay
in the head-folded [B, S, H*D] lane layout the QKV projection GEMM emits,
so the BSHD<->BHSD transposes disappear (8.6 ms of the GPT-2-Large cell's
153 ms step on a v5e, for 8.0 ms more in kernels that still compute whole
tiles: +1.03% tokens/s in every round, PERF.md section 6, PR 54). Per-head
access is expressed as static lane-block slices in the BlockSpec index
maps — the grid stays per-(head group), preserving Mosaic's
cross-grid-step pipelining (NOT the rejected in-kernel ``fori`` designs,
PERFLOG items 1-4): one grid step covers a lane-aligned *group* of heads
(a pair for MHA d=64) and a short static unroll walks the group.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.observability.registry import MetricsRegistry
from deepspeed_tpu.parallel.topology import GROUP_ALIASES
from deepspeed_tpu.utils.platform import kernel_names, on_tpu

NEG_INF = -1e30
# The tiles, from what a v5e measured for the three bshd kernels alone at
# the two training cells' shapes ([8, 20, 1024, 64] and [1, 16, 4096, 128]
# with 4 KV heads; PERF.md section 6, PR 49; microseconds a call, forward /
# dQ / dK/dV): a k-tile of 1024 keys beats 512 (the forward at one k-tile
# needs no scratch: 749 against 1,409 at d64) and 2048 does not fit the
# scoped VMEM at d128; under it a q-tile of 256 / 512 / 1024 rows reads
# 789 / 634 / 591, 1,092 / 985 / 916 and 1,699 / 1,398 / 1,291 at d64 once
# a crossed tile does its live part only (a grid step's fixed ~0.35 us, and
# nothing of a larger tile is dead work any more).  A call that is not
# causal, and the folded family, keep the 512 rows they were measured
# with (the [512, 1024] fp32 score tile, 2 MB, is the largest
# block any body multiplies: ``_SCORE_ELEMS``).
DEFAULT_BLOCK_Q = 512
CAUSAL_BLOCK_Q = 1024
DEFAULT_BLOCK_K = 1024
MIN_BLOCK = 128


def _pick_block(n: int, target: int) -> int:
    """Largest multiple of MIN_BLOCK that divides n, capped at target
    (n itself when n < MIN_BLOCK)."""
    if n <= MIN_BLOCK:
        return n
    best = MIN_BLOCK
    b = MIN_BLOCK
    while b <= min(n, target):
        if n % b == 0:
            best = b
        b += MIN_BLOCK
    return best


# --------------------------------------------------------------------- #
# Mesh partitioning.  GSPMD cannot partition a compiled Mosaic kernel
# ("Mosaic kernels cannot be automatically partitioned. Please wrap the
# call in a shard_map") — so on a multi-device mesh the public entries
# run the kernel once per shard: attention is independent per batch row
# and per head, nothing crosses shards.  The interpreter lowers to plain
# XLA ops that GSPMD splits itself and needs none of this.
# --------------------------------------------------------------------- #
_BATCH_AXES = GROUP_ALIASES["dp"]
_HEAD_AXES = ("seq", "model")   # Ulysses scatters heads over 'seq',
#                                 tensor parallelism over 'model'


def mesh_partition(batch: int, num_heads: int, num_kv_heads: int,
                   heads_ok=lambda n: True):
    """``(mesh, batch_axes, head_axes, head_shards)`` for splitting one
    attention call over the engine's mesh, or None when there is nothing
    to split: no topology, one device, or a caller that is already
    inside a manual (``shard_map``) region and holds local shards.

    Batch goes over the data-parallel axes and heads over 'seq' x
    'model', each only when it divides (``heads_ok(n)`` lets a layout
    veto a head split its tiling cannot take); a dimension that does not
    divide stays whole on every shard, which is redundant but correct."""
    import math

    from deepspeed_tpu.parallel import groups

    topo = groups.get_topology(optional=True)
    if topo is None or topo.world_size == 1 or \
            jax.sharding.get_abstract_mesh().manual_axes:
        return None
    mesh = topo.mesh
    size = lambda axes: math.prod(mesh.shape[a] for a in axes)
    batch_axes = _BATCH_AXES if batch % size(_BATCH_AXES) == 0 else None
    head_axes = tuple(a for a in _HEAD_AXES if mesh.shape[a] > 1)
    n = size(head_axes)
    if n == 1 or num_heads % n or num_kv_heads % n or not heads_ok(n):
        head_axes, n = None, 1
    return mesh, batch_axes, head_axes, n


def run_partitioned(kernel, q, k, v, part):
    """``kernel(q, k, v, head_shards)`` per shard of the partition from
    :func:`mesh_partition` (one whole call when it is None); heads are
    dim 2 of [B,S,H,D] and of the folded [B,S,H*D] alike."""
    from jax.sharding import PartitionSpec as P

    if part is None:
        return kernel(q, k, v, 1)
    mesh, batch_axes, head_axes, n = part
    spec = P(batch_axes, None, head_axes, *([None] * (q.ndim - 3)))
    return jax.shard_map(
        lambda a, b, c: kernel(a, b, c, n), mesh=mesh,
        in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False)(q, k, v)


def flash_attention_usable(q, k, v, causal, mask) -> bool:
    """Shapes/platform for which the kernel path is profitable and valid."""
    if mask is not None:  # custom masks take the XLA path
        return False
    b, sq, h, d = q.shape
    _, sk, hkv, _ = k.shape
    if h % hkv != 0 or d % 8 != 0:
        return False
    if sq % _pick_block(sq, DEFAULT_BLOCK_Q) != 0 or \
            sk % _pick_block(sk, DEFAULT_BLOCK_K) != 0:
        return False
    if sq * sk < 128 * 128:  # tiny: XLA fusion wins
        return False
    return on_tpu()


def _causal_keep(iq, ik, block_q, block_k, causal_offset, window):
    """[bq, bk] bool tile of visible (row, col) pairs for q-block iq x
    k-block ik under end-aligned causal masking (+ optional sliding
    window) — shared by every kernel variant in this file."""
    rows = iq * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)
    cols = ik * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    keep = rows + causal_offset >= cols
    if window is not None:
        keep = jnp.logical_and(keep, cols > rows + causal_offset - window)
    return keep


def _run_predicate(iq, ik, block_q, block_k, causal, causal_offset, window):
    """Whether q-block iq x k-block ik intersects the visible band at all
    (skip blocks fully above the causal diagonal / below the window)."""
    run = jnp.logical_or(not causal,
                         (iq + 1) * block_q - 1 + causal_offset >= ik * block_k)
    if window is not None:
        run = jnp.logical_and(
            run,
            (ik + 1) * block_k - 1 > iq * block_q + causal_offset - window)
    return run


# ===================================================================== #
# A causal tile's live part
# ===================================================================== #
# What the band ``0 <= u < window``, ``u = row + causal_offset - col``,
# leaves of a (q-tile, k-tile) pair depends on one number, the pair's
# displacement ``u0 = iq * block_q + causal_offset - ik * block_k`` (``u`` of
# the pair's first row at its first key), and a grid meets few of them: the
# pairs under the band are all alike, and so are the pairs an edge crosses
# at the same place (one displacement, 0, at tiles of 1024 x 1024 and a
# ``causal_offset`` that is a multiple of 1024).  So each kernel holds, beside the
# bare body for a pair inside the band (no iota, no compare, no select), one
# body a crossed displacement in which everything is STATIC: the pair is cut
# into sub-blocks of ``sub_q`` rows by ``sub_k`` keys (``_sub_blocks``), a
# group of rows (forward, dQ) or of keys (dK/dV) takes the sub-blocks the
# band leaves it as slices of static width, side by side in one product,
# and only the run of sub-blocks an edge crosses builds a mask.  A sub-block
# wholly outside the band is in no product and no exponential.  The bodies
# are picked by ``pl.when`` on ``u0``.  (The same rule as a LOOP inside one
# body, its trip counts from the grid indices, measured 1.2-1.8x SLOWER than
# the unmasked-everywhere parent on a v5e: the steps of a compiled loop do
# not overlap and each product is too small to hide its own latency;
# smaller grid tiles lost too: PERF.md section 6, PR 49.)
#
# ``_live_runs`` is the rule, on Python ints; ``causal_work`` counts with it
# what the kernels execute.

#: rows and keys of a sub-block, where the tile divides by it
SUB_BLOCK_Q = 256
SUB_BLOCK_K = 256
#: crossed displacements a kernel holds a body for; a grid with more (a
#: ``causal_offset`` that is no multiple of the tiles) runs the rest as
#: whole masked tiles, which is what every crossed pair ran before PR 49
MAX_TILE_BODIES = 6
#: score elements of the largest block a body multiplies at once (2 MB of
#: float32, of which the compiler keeps about three)
_SCORE_ELEMS = 512 * 1024


def _sub_blocks(block_q: int, block_k: int) -> tuple:
    """``(sub_q, sub_k)`` of a ``block_q x block_k`` tile."""
    return (_pick_block(block_q, SUB_BLOCK_Q),
            _pick_block(block_k, SUB_BLOCK_K))


def _group(u0, block: int, sub: int, other: int) -> int:
    """Rows (forward, dQ) or keys (dK/dV) a body takes at a time: a
    sub-block where the tile's live part is cut out (``u0`` an int), else
    as much of the tile as ``_SCORE_ELEMS`` allows against ``other``
    elements of the other side."""
    if isinstance(u0, int):
        return sub
    return _pick_block(block, max(MIN_BLOCK, _SCORE_ELEMS // other))


def _live_runs(u_lo: int, u_hi: int, step: int, n: int, window) -> list:
    """What the band leaves of ``n`` sub-blocks in a row, sub-block ``j``
    holding ``u`` from ``u_lo + j * step`` to ``u_hi + j * step`` (``step``
    elements a sub-block, negative along the keys): ``[(first element,
    elements, masked)]``, a run of neighbours of one kind each.  A sub-block
    is taken where some ``u`` is inside ``0 <= u < window`` and masked
    where not every one is."""
    top = float("inf") if window is None else window - 1
    sub, runs = abs(step), []
    for j in range(n):
        lo, hi = u_lo + j * step, u_hi + j * step
        if hi < 0 or lo > top:
            continue
        masked = lo < 0 or hi > top
        if runs and runs[-1][2] == masked and sum(runs[-1][:2]) == j * sub:
            runs[-1][1] += sub
        else:
            runs.append([j * sub, sub, masked])
    return [tuple(r) for r in runs]


def _key_steps(u0, r: int, rows: int, block_k: int, sub_k: int, window):
    """The products of the group of ``rows`` rows from row ``r`` of a tile
    at displacement ``u0``, ``[(first key, keys, masked)]``: the whole tile
    bare (``u0`` None: a pair inside the band, or a call that is not
    causal), its live part (an int), or the whole tile masked (the kernel's
    scalar: a crossed pair that has no body of its own)."""
    if not isinstance(u0, int):
        return [(0, block_k, u0 is not None)]
    return _live_runs(u0 + r - sub_k + 1, u0 + r + rows - 1, -sub_k,
                      block_k // sub_k, window)


def _row_steps(u0, c: int, cols: int, block_q: int, sub_q: int, window):
    """``_key_steps`` for the group of ``cols`` keys from key ``c``:
    ``[(first row, rows, masked)]``."""
    if not isinstance(u0, int):
        return [(0, block_q, u0 is not None)]
    return _live_runs(u0 - c - cols + 1, u0 - c + sub_q - 1, sub_q,
                      block_q // sub_q, window)


def _tile_bodies(nq: int, nk: int, block_q: int, block_k: int,
                 causal_offset: int, window) -> tuple:
    """The displacements of the grid's pairs that an edge of the band
    crosses, the most frequent first."""
    sub_q, sub_k = _sub_blocks(block_q, block_k)
    crossed = {}
    for iq in range(nq):
        for ik in range(nk):
            u0 = iq * block_q + causal_offset - ik * block_k
            steps = [s for r in range(0, block_q, sub_q)
                     for s in _key_steps(u0, r, sub_q, block_k, sub_k,
                                         window)]
            taken = sum(width for _, width, _ in steps)
            if 0 < taken < block_k * (block_q // sub_q) or any(
                    masked for _, _, masked in steps):
                crossed[u0] = crossed.get(u0, 0) + 1
    return tuple(sorted(crossed, key=lambda u0: -crossed[u0]))


def _by_tile_class(iq, ik, body, causal, block_q, block_k, causal_offset,
                   window, bodies):
    """``body(u0)`` for this grid step's pair, by what the band leaves of
    it: nothing for a pair outside the band, ``body(None)`` for one inside
    it, ``body(<int>)`` for a crossed pair whose displacement is among
    ``bodies``, ``body(<the scalar>)`` for any other."""
    if not causal:
        return body(None)
    u0 = iq * block_q + causal_offset - ik * block_k
    inside = u0 >= block_k - 1
    if window is not None:
        inside = jnp.logical_and(inside, u0 + block_q - 1 < window)
    pl.when(inside)(lambda: body(None))
    for c in bodies[:MAX_TILE_BODIES]:
        pl.when(u0 == c)(functools.partial(body, c))
    if bodies[MAX_TILE_BODIES:]:
        pl.when(functools.reduce(jnp.logical_or, [
            u0 == c for c in bodies[MAX_TILE_BODIES:]]))(lambda: body(u0))


def _held_k_tile(iq, ik, *, causal, block_q, block_k, nk, causal_offset,
                 window):
    """``ik`` held inside the k-tiles that q-tile ``iq`` meets: a grid step
    outside the band names the tile of the nearest step inside it, so the
    pipeline fetches no keys for a step that does no work."""
    if not causal:
        return ik
    last = jax.lax.div(
        jnp.maximum((iq + 1) * block_q - 1 + causal_offset, 0), block_k)
    ik = jnp.minimum(ik, jnp.minimum(last, nk - 1))
    if window is not None:
        first = jax.lax.div(
            jnp.maximum(iq * block_q + causal_offset - window + 1, 0),
            block_k)
        ik = jnp.maximum(ik, jnp.minimum(first, nk - 1))
    return ik


def _held_q_tile(ik, iq, *, causal, block_q, block_k, nq, causal_offset,
                 window):
    """``iq`` held inside the q-tiles that meet k-tile ``ik`` (dK/dV's
    inner axis): the mirror of ``_held_k_tile``."""
    if not causal:
        return iq
    first = jax.lax.div(jnp.maximum(ik * block_k - causal_offset, 0),
                        block_q)
    iq = jnp.maximum(iq, jnp.minimum(first, nq - 1))
    if window is not None:
        last = jax.lax.div(
            jnp.maximum((ik + 1) * block_k - 2 - causal_offset + window, 0),
            block_q)
        iq = jnp.minimum(iq, jnp.minimum(last, nq - 1))
    return iq


def _band_keep(rows: int, cols: int, u0, window):
    """Visible elements of a ``rows x cols`` block whose first row and first
    key stand at ``u0``: ``0 <= u0 + row - col < window``."""
    col_minus_row = jax.lax.broadcasted_iota(jnp.int32, (rows, cols), 1) - \
        jax.lax.broadcasted_iota(jnp.int32, (rows, cols), 0)
    keep = col_minus_row <= u0
    if window is not None:
        keep = jnp.logical_and(keep, col_minus_row > u0 - window)
    return keep


def causal_work(sq: int, sk: int, *, causal: bool = True,
                window: Optional[int] = None,
                block_q: Optional[int] = None,
                block_k: Optional[int] = None) -> tuple:
    """``(score elements each of the three bshd kernels executes, score
    elements the mask leaves alive)`` of one head of one sequence, at the
    blocks ``flash_attention`` would pick: the kernels' own rule."""
    import numpy as np

    if not causal:
        return sq * sk, sq * sk
    block_q = block_q or _pick_block(sq, CAUSAL_BLOCK_Q)
    block_k = block_k or _pick_block(sk, DEFAULT_BLOCK_K)
    sub_q, sub_k = _sub_blocks(block_q, block_k)
    offset = sk - sq
    whole = _tile_bodies(sq // block_q, sk // block_k, block_q, block_k,
                         offset, window)[MAX_TILE_BODIES:]
    run = 0
    for q0 in range(0, sq, block_q):
        for k0 in range(0, sk, block_k):
            u0 = q0 + offset - k0
            # a crossed pair with no body of its own runs whole
            run += block_q * block_k if u0 in whole else sum(
                sub_q * width for r in range(0, block_q, sub_q)
                for _, width, _ in _key_steps(u0, r, sub_q, block_k, sub_k,
                                              window))
    newest = np.arange(sq) + offset                 # a row's newest key
    oldest = newest - (window or sk + sq) + 1
    live = np.clip(np.minimum(newest, sk - 1)
                   - np.maximum(oldest, 0) + 1, 0, None).sum()
    return run, int(live)


#: how far the rule engages at the shapes a program really uses: the score
#: elements each bshd kernel executes and those the mask leaves alive, summed
#: over every ``flash_attention`` call this process has traced (``causal_work``
#: x batch x heads, once where a call is traced, not once a step)
_WORK = {"flash/score_elems_run": 0, "flash/score_elems_live": 0}
for _name in _WORK:
    MetricsRegistry.default().counter(
        _name, help="score elements of the flash_attention calls traced")
MetricsRegistry.default().register_provider("flash", lambda: dict(_WORK))


# ===================================================================== #
# Forward
# ===================================================================== #
def _stat_lanes(block_k: int) -> int:
    """Lanes a row's softmax statistics take: 128 where the keys come in
    whole lane tiles (the maximum in every lane, the sum as 128 partial
    sums, so an update crosses the lanes once a row, for the maximum: the
    serving tiled read's form, PERF.md section 6, PR 44), else one column."""
    return 128 if block_k % 128 == 0 else 1


def _fold(x, w: int, op, over_row):
    """[rows, keys] -> [rows, w]: ``op`` over the keys' lane tiles (w 1:
    ``over_row`` over the whole row)."""
    if w == 1:
        return over_row(x, axis=1, keepdims=True)
    return functools.reduce(
        op, [x[:, c * w:(c + 1) * w] for c in range(x.shape[1] // w)])


def _spread(x, n: int):
    """[rows, w] -> [rows, n]: the row's value in every lane."""
    w = x.shape[1]
    if w == 1 or n == w:
        return x
    return x[:, :n] if n < w else jnp.tile(x, (1, n // w))


def _fwd_rows(q, k_ref, v_ref, steps, u0, r, carry, *, w, window):
    """The softmax of one group of rows over the keys its ``steps`` name
    (``_key_steps``), each a product of static width.  ``q`` [rows, d]
    (pre-scaled); ``carry`` = (m, l, acc), float32: the running maximum
    [rows, w], the running sum [rows, w] (``_stat_lanes``) and the
    accumulator [rows, d], or None before the first keys of a row; ``u0`` =
    the tile's displacement and ``r`` the group's first row in the tile
    (read by a masked step alone)."""
    rows, d = q.shape
    for c, width, masked in steps:
        # dots take the INPUT dtype (bf16) and accumulate fp32 via
        # preferred_element_type — an fp32×fp32 MXU dot runs at ~1/8 the
        # bf16 rate on TPU. q arrives pre-scaled, so no [rows, keys]
        # scale pass.
        kb = k_ref[0, 0, c:c + width, :]               # [width, d] bf16
        vb = v_ref[0, 0, c:c + width, :]
        s = jax.lax.dot_general(
            q, kb, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)        # [rows, width] f32
        if masked:
            s = jnp.where(_band_keep(rows, width, u0 + r - c, window),
                          s, NEG_INF)
        m_new = jnp.max(_fold(s, w, jnp.maximum, jnp.max), axis=1,
                        keepdims=True)
        m_new = jnp.broadcast_to(m_new, (rows, w)) if carry is None \
            else jnp.maximum(carry[0], m_new)
        p = jnp.exp(s - _spread(m_new, width))
        l_new = _fold(p, w, jnp.add, jnp.sum)
        acc = jax.lax.dot_general(
            p.astype(vb.dtype), vb, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        if carry is not None:
            corr = jnp.exp(carry[0] - m_new)           # [rows, w]
            l_new = carry[1] * corr + l_new
            acc = carry[2] * _spread(corr, d) + acc
        carry = m_new, l_new, acc
    return carry


def _fwd_finish(m, l, acc, o_dtype):
    """(o [rows, d], lse [rows, 8]) of rows that have met all their keys."""
    l = jnp.sum(l, axis=1, keepdims=True)             # [rows, 1]
    safe_l = jnp.where(l == 0.0, 1.0, l)
    return (acc / safe_l).astype(o_dtype), jnp.broadcast_to(
        m[:, :1] + jnp.log(safe_l), (m.shape[0], 8))


def _fwd_kernel_onepass(q_ref, k_ref, v_ref, o_ref, lse_ref, *, causal,
                        block_q, block_k, causal_offset, window, bodies):
    """Single-k-block forward (nk == 1): every key a row sees is in the one
    tile, so a group of rows meets its live keys in one plain softmax (two
    products where the diagonal splits them into a bare and a masked run)
    and writes ``o`` and ``lse`` once: no scratch, no init / finish steps of
    the grid. q arrives pre-scaled (see flash_attention)."""
    d = q_ref.shape[-1]
    w = _stat_lanes(block_k)
    sub_q, sub_k = _sub_blocks(block_q, block_k)

    def body(u0):
        group = _group(u0, block_q, sub_q, block_k)
        for r in range(0, block_q, group):
            rows = slice(r, r + group)
            steps = _key_steps(u0, r, group, block_k, sub_k, window)
            carry = _fwd_rows(q_ref[0, 0, rows, :], k_ref, v_ref, steps,
                              u0, r, None, w=w, window=window)
            if carry is None:           # rows that see no key (sq > sk)
                carry = (jnp.full((group, w), NEG_INF, jnp.float32),
                         jnp.zeros((group, w), jnp.float32),
                         jnp.zeros((group, d), jnp.float32))
            o_ref[0, 0, rows, :], lse_ref[0, 0, rows, :] = _fwd_finish(
                *carry, o_ref.dtype)

    _by_tile_class(pl.program_id(2), 0, body, causal, block_q,
                   block_k, causal_offset, window, bodies)


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref,
                acc_ref, m_ref, l_ref, *, causal, block_q, block_k,
                num_k_blocks, causal_offset, window, bodies):
    ik = pl.program_id(3)
    sub_q, sub_k = _sub_blocks(block_q, block_k)

    @pl.when(ik == 0)
    def _():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    def body(u0):
        group = _group(u0, block_q, sub_q, block_k)
        for r in range(0, block_q, group):
            rows = slice(r, r + group)
            steps = _key_steps(u0, r, group, block_k, sub_k, window)
            if steps:
                m_ref[rows], l_ref[rows], acc_ref[rows] = _fwd_rows(
                    q_ref[0, 0, rows, :], k_ref, v_ref, steps, u0, r,
                    (m_ref[rows], l_ref[rows], acc_ref[rows]),
                    w=m_ref.shape[1], window=window)

    _by_tile_class(pl.program_id(2), ik, body, causal, block_q,
                   block_k, causal_offset, window, bodies)

    @pl.when(ik == num_k_blocks - 1)
    def _():
        o_ref[0, 0], lse_ref[0, 0] = _fwd_finish(
            m_ref[:], l_ref[:], acc_ref[:], o_ref.dtype)


# ``_fwd`` and ``_bwd`` are jitted so that the layers of a model, which call
# them at one set of shapes, trace and lower the kernels ONCE a process: a
# body a crossed displacement is a few hundred equations, and the GPT-2
# cell's set-up traced them 70 times (PERF.md section 6, PR 49).
@functools.partial(jax.jit, static_argnames=(
    "causal", "block_q", "block_k", "interpret", "window"))
def _fwd(q, k, v, *, causal, block_q, block_k, interpret, window=None):
    """q (PRE-SCALED):[B,H,Sq,D] k/v:[B,Hkv,Sk,D]
    -> (o:[B,H,Sq,D], lse:[B,H,Sq,8])."""
    b, h, sq, d = q.shape
    _, hkv, sk, _ = k.shape
    g = h // hkv
    nq = sq // block_q
    nk = sk // block_k
    band = dict(causal=causal, block_q=block_q, block_k=block_k,
                causal_offset=sk - sq, window=window)
    tile = dict(band, bodies=_tile_bodies(
        nq, nk, block_q, block_k, sk - sq, window) if causal else ())

    if nk == 1:
        kernel = functools.partial(_fwd_kernel_onepass, **tile)
        grid = (b, h, nq)
        idx_q = lambda b_, h_, iq: (b_, h_, iq, 0)
        idx_k = lambda b_, h_, iq: (b_, h_ // g, 0, 0)
        idx_l = lambda b_, h_, iq: (b_, h_, iq, 0)
        scratch = []
    else:
        kernel = functools.partial(_fwd_kernel, num_k_blocks=nk, **tile)
        grid = (b, h, nq, nk)
        idx_q = lambda b_, h_, iq, ik: (b_, h_, iq, 0)
        idx_k = lambda b_, h_, iq, ik: (b_, h_ // g, _held_k_tile(
            iq, ik, nk=nk, **band), 0)
        idx_l = lambda b_, h_, iq, ik: (b_, h_, iq, 0)
        lanes = _stat_lanes(block_k)
        scratch = [
            pltpu.VMEM((block_q, d), jnp.float32),
            pltpu.VMEM((block_q, lanes), jnp.float32),
            pltpu.VMEM((block_q, lanes), jnp.float32),
        ]
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d), idx_q),
            pl.BlockSpec((1, 1, block_k, d), idx_k),
            pl.BlockSpec((1, 1, block_k, d), idx_k),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_q, d), idx_q),
            # lse is logically 1-D per (b, h); stored 8 wide (the narrowest
            # minor dim the TPU lowering accepts) — the 128-wide copy here
            # cost ~100 MB of fp32 HBM traffic per layer on the 125M bench
            pl.BlockSpec((1, 1, block_q, 8), idx_l),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, sq, d), q.dtype),
            jax.ShapeDtypeStruct((b, h, sq, 8), jnp.float32),
        ],
        scratch_shapes=scratch,
        interpret=interpret,
        **kernel_names(kernel),
    )(q, k, v)


# ===================================================================== #
# Backward
# ===================================================================== #
def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
                   dq_acc, *, scale, causal, block_q, block_k, num_k_blocks,
                   causal_offset, window, bodies):
    # q arrives pre-scaled: s needs no scale; dq needs one final *scale on
    # the small [bq, d] accumulator (dL/dq = scale * dL/dq_scaled)
    ik = pl.program_id(3)
    sub_q, sub_k = _sub_blocks(block_q, block_k)

    @pl.when(ik == 0)
    def _():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    def body(u0):
        group = _group(u0, block_q, sub_q, block_k)
        for r in range(0, block_q, group):  # static unroll over row groups
            rows = slice(r, r + group)
            q = q_ref[0, 0, rows, :]
            do = do_ref[0, 0, rows, :]
            lse = lse_ref[0, 0, rows, :][:, :1]           # [group, 1]
            delta = delta_ref[0, 0, rows, :][:, :1]       # [group, 1]
            for c, width, masked in _key_steps(u0, r, group, block_k, sub_k,
                                               window):
                # bf16 MXU dots with fp32 accumulation (see _fwd_rows)
                kb = k_ref[0, 0, c:c + width, :]
                vb = v_ref[0, 0, c:c + width, :]
                s = jax.lax.dot_general(q, kb, (((1,), (1,)), ((), ())),
                                        preferred_element_type=jnp.float32)
                if masked:
                    s = jnp.where(_band_keep(group, width, u0 + r - c,
                                             window), s, NEG_INF)
                p = jnp.exp(s - lse)                  # [group, width] f32
                dp = jax.lax.dot_general(do, vb, (((1,), (1,)), ((), ())),
                                         preferred_element_type=jnp.float32)
                ds = (p * (dp - delta)).astype(kb.dtype)
                dq_acc[rows] = dq_acc[rows] + jax.lax.dot_general(
                    ds, kb, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)

    _by_tile_class(pl.program_id(2), ik, body, causal, block_q,
                   block_k, causal_offset, window, bodies)

    @pl.when(ik == num_k_blocks - 1)
    def _():
        dq_ref[0, 0] = (dq_acc[:] * scale).astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_acc, dv_acc, *, causal,
                    block_q, block_k, num_q_blocks, causal_offset, window,
                    bodies):
    # q arrives pre-scaled: dL/dk = ds^T @ (scale*q) needs no extra scale
    iq = pl.program_id(3)
    sub_q, sub_k = _sub_blocks(block_q, block_k)

    @pl.when(iq == 0)
    def _():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    def body(u0):
        group = _group(u0, block_k, sub_k, block_q)
        for c in range(0, block_k, group):  # static unroll over key groups
            cols = slice(c, c + group)
            kb = k_ref[0, 0, cols, :]
            vb = v_ref[0, 0, cols, :]
            for r, height, masked in _row_steps(u0, c, group, block_q, sub_q,
                                                window):
                # bf16 MXU dots with fp32 accumulation (see _fwd_rows)
                q = q_ref[0, 0, r:r + height, :]
                do = do_ref[0, 0, r:r + height, :]
                lse = lse_ref[0, 0, r:r + height, :][:, :1]
                delta = delta_ref[0, 0, r:r + height, :][:, :1]
                s = jax.lax.dot_general(q, kb, (((1,), (1,)), ((), ())),
                                        preferred_element_type=jnp.float32)
                if masked:
                    s = jnp.where(_band_keep(height, group, u0 + r - c,
                                             window), s, NEG_INF)
                p = jnp.exp(s - lse)                  # [height, group] f32
                dv_acc[cols] = dv_acc[cols] + jax.lax.dot_general(
                    p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)   # [group, d]
                dp = jax.lax.dot_general(do, vb, (((1,), (1,)), ((), ())),
                                         preferred_element_type=jnp.float32)
                ds = (p * (dp - delta)).astype(q.dtype)
                dk_acc[cols] = dk_acc[cols] + jax.lax.dot_general(
                    ds, q, (((0,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)

    _by_tile_class(iq, pl.program_id(2), body, causal, block_q,
                   block_k, causal_offset, window, bodies)

    @pl.when(iq == num_q_blocks - 1)
    def _():
        dk_ref[0, 0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[:].astype(dv_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "scale", "causal", "block_q", "block_k", "interpret", "window"))
def _bwd(res, grads, *, scale, causal, block_q, block_k, interpret,
         window=None):
    q, k, v, o, lse = res  # q is the PRE-SCALED query
    do = grads[0]
    b, h, sq, d = q.shape
    _, hkv, sk, _ = k.shape
    g = h // hkv
    nq = sq // block_q
    nk = sk // block_k
    band = dict(causal=causal, block_q=block_q, block_k=block_k,
                causal_offset=sk - sq, window=window)
    tile = dict(band, bodies=_tile_bodies(
        nq, nk, block_q, block_k, sk - sq, window) if causal else ())
    # a grid step outside the band fetches nothing (``_held_k_tile``)
    held_k = lambda iq, ik: _held_k_tile(iq, ik, nk=nk, **band)
    held_q = lambda ik, iq: _held_q_tile(ik, iq, nq=nq, **band)

    # delta_i = rowsum(dO_i * O_i) — cheap, let XLA fuse it; 8 wide (see
    # the lse layout note in _fwd)
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    delta = jnp.broadcast_to(delta[..., None], delta.shape + (8,))

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, num_k_blocks=nk,
                          **tile),
        grid=(b, h, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d),
                         lambda b_, h_, iq, ik: (b_, h_, iq, 0)),
            pl.BlockSpec((1, 1, block_k, d), lambda b_, h_, iq, ik: (
                b_, h_ // g, held_k(iq, ik), 0)),
            pl.BlockSpec((1, 1, block_k, d), lambda b_, h_, iq, ik: (
                b_, h_ // g, held_k(iq, ik), 0)),
            pl.BlockSpec((1, 1, block_q, d),
                         lambda b_, h_, iq, ik: (b_, h_, iq, 0)),
            pl.BlockSpec((1, 1, block_q, 8),
                         lambda b_, h_, iq, ik: (b_, h_, iq, 0)),
            pl.BlockSpec((1, 1, block_q, 8),
                         lambda b_, h_, iq, ik: (b_, h_, iq, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, d),
                               lambda b_, h_, iq, ik: (b_, h_, iq, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        interpret=interpret,
        **kernel_names(_bwd_dq_kernel),
    )(q, k, v, do, lse, delta)

    # dK/dV per q-head, then sum each GQA group
    dk_h, dv_h = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, num_q_blocks=nq, **tile),
        grid=(b, h, nk, nq),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d), lambda b_, h_, ik, iq: (
                b_, h_, held_q(ik, iq), 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda b_, h_, ik, iq: (b_, h_ // g, ik, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda b_, h_, ik, iq: (b_, h_ // g, ik, 0)),
            pl.BlockSpec((1, 1, block_q, d), lambda b_, h_, ik, iq: (
                b_, h_, held_q(ik, iq), 0)),
            pl.BlockSpec((1, 1, block_q, 8), lambda b_, h_, ik, iq: (
                b_, h_, held_q(ik, iq), 0)),
            pl.BlockSpec((1, 1, block_q, 8), lambda b_, h_, ik, iq: (
                b_, h_, held_q(ik, iq), 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_k, d),
                         lambda b_, h_, ik, iq: (b_, h_, ik, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda b_, h_, ik, iq: (b_, h_, ik, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, sk, d), k.dtype),
            jax.ShapeDtypeStruct((b, h, sk, d), v.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((block_k, d), jnp.float32),
                        pltpu.VMEM((block_k, d), jnp.float32)],
        interpret=interpret,
        **kernel_names(_bwd_dkv_kernel),
    )(q, k, v, do, lse, delta)

    if g > 1:
        dk = dk_h.reshape(b, hkv, g, sk, d).sum(axis=2)
        dv = dv_h.reshape(b, hkv, g, sk, d).sum(axis=2)
    else:
        dk, dv = dk_h, dv_h
    return dq, dk.astype(k.dtype), dv.astype(v.dtype)


# ===================================================================== #
# Public entry
# ===================================================================== #
@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _flash(q, k, v, scale, causal, block_q, block_k, interpret, window):
    # fold the softmax scale into q once ([B,H,S,D] — 16x smaller than one
    # [bq, bk] pass per tile inside the kernel)
    qs = (q.astype(jnp.float32) * scale).astype(q.dtype)
    o, _ = _fwd(qs, k, v, causal=causal, block_q=block_q,
                block_k=block_k, interpret=interpret, window=window)
    return o


def _flash_fwd(q, k, v, scale, causal, block_q, block_k, interpret, window):
    qs = (q.astype(jnp.float32) * scale).astype(q.dtype)
    o, lse = _fwd(qs, k, v, causal=causal, block_q=block_q,
                  block_k=block_k, interpret=interpret, window=window)
    return o, (qs, k, v, o, lse)


def _flash_bwd(scale, causal, block_q, block_k, interpret, window, res, g):
    return _bwd(res, (g,), scale=scale, causal=causal, block_q=block_q,
                block_k=block_k, interpret=interpret, window=window)


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(q, k, v, *, causal: bool = True,
                    mask: Optional[jax.Array] = None,
                    scale: Optional[float] = None,
                    window: Optional[int] = None,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None,
                    interpret: Optional[bool] = None):
    """Flash attention. q: [B,Sq,H,D]; k/v: [B,Sk,Hkv,D]; returns [B,Sq,H,D].

    ``window`` (requires ``causal``) restricts each query to the previous
    ``window`` keys — Mistral sliding-window attention, with out-of-band
    k-blocks skipped entirely (O(s*w) work, no dense mask).

    ``interpret=None`` auto-selects the Pallas interpreter off-TPU so the
    exact kernel code is testable on the CPU mesh.
    """
    if mask is not None:
        raise NotImplementedError(
            "flash_attention supports causal/full (+sliding window) only; "
            "use ops.attention.dot_product_attention for custom masks")
    if window is not None and not causal:
        raise ValueError("sliding window requires causal attention")
    if window is not None and window <= 0:
        raise ValueError(f"window must be positive, got {window}")
    b, sq, h, d = q.shape
    _, sk, hkv, _ = k.shape
    if h % hkv != 0:
        raise ValueError(f"GQA needs H % Hkv == 0, got {h} % {hkv}")
    scale = scale if scale is not None else 1.0 / (d ** 0.5)
    block_q = block_q or _pick_block(
        sq, CAUSAL_BLOCK_Q if causal else DEFAULT_BLOCK_Q)
    block_k = block_k or _pick_block(sk, DEFAULT_BLOCK_K)
    if sq % block_q or sk % block_k:
        raise ValueError(
            f"seq lengths ({sq},{sk}) must divide blocks ({block_q},{block_k})")
    if interpret is None:
        interpret = not on_tpu()
    run, live = causal_work(sq, sk, causal=causal, window=window,
                            block_q=block_q, block_k=block_k)
    _WORK["flash/score_elems_run"] += b * h * run
    _WORK["flash/score_elems_live"] += b * h * live

    def kernel(q, k, v, _head_shards):
        o = _flash(q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
                   v.transpose(0, 2, 1, 3), float(scale), bool(causal),
                   int(block_q), int(block_k), bool(interpret),
                   int(window) if window is not None else None)
        return o.transpose(0, 2, 1, 3)

    return run_partitioned(
        kernel, q, k, v, None if interpret else mesh_partition(b, h, hkv))


# ===================================================================== #
# Folded-layout ("layout-native") variant: q/k/v in [B, S, H*D]
# ===================================================================== #
# The projection GEMM emits [B, S, H*D]; the kernels below consume it
# directly. Head h lives in lanes [h*d, (h+1)*d) — a BlockSpec block of
# ``hb`` heads (hb*d lanes) per grid step keeps every DMA window 128-lane
# aligned. The grid is per head-GROUP (hb heads), so Mosaic still
# software-pipelines DMA/MXU/VPU across grid steps; inside a step a short
# STATIC python unroll (hb <= 4, typically 2) walks the group with
# static lane slices. lse/delta stay head-major [B, H, S, 8] (tiny).

# VMEM guard, from the TPU compiler itself (every kernel of the family
# compiled for a described v5e at the default 512 x 1024 tiles, 1,024 and
# 4,096 tokens: tools/chip_calls/pr54_results/aot_route.txt): groups of 2
# and 4 heads on 128 or 256 lanes fit; 6 or 8 heads a group (hb fp32
# [bq, bk] score tiles) and 4 heads of 96 lanes (384) run out of VMEM.
_FOLDED_MAX_HEADS_PER_BLOCK = 4
_FOLDED_MAX_LANES_PER_BLOCK = 256


def folded_heads_per_block(num_heads: int, num_kv_heads: int,
                           head_dim: int) -> Optional[int]:
    """Query heads per grid step for the folded layout, or None when the
    geometry has no lane-aligned grouping.

    The family serves heads narrower than a 128-lane tile (at 128 lanes
    the [B, H, S, D] kernels measured 3.3% more tokens/s: PERF.md
    section 6, PR 54): a group of ``m = 128/gcd(d,128)`` heads spans whole lane
    tiles; the group is widened to ``m * g`` so the KV heads it touches
    also form whole tiles (g = GQA group size).
    """
    d, h, hkv = head_dim, num_heads, num_kv_heads
    if d % 8 != 0 or d >= 128 or h % hkv != 0:
        return None
    import math

    m = 128 // math.gcd(d, 128)
    hb = m * (h // hkv)
    if hb > _FOLDED_MAX_HEADS_PER_BLOCK or \
            hb * d > _FOLDED_MAX_LANES_PER_BLOCK or h % hb != 0:
        return None
    return hb


def _fwd_kernel_folded_onepass(q_ref, k_ref, v_ref, o_ref, lse_ref, *,
                               causal, block_q, block_k, causal_offset,
                               window, hb, g, d):
    """Single-k-block folded forward: whole key range visible, plain
    softmax per head of the group (see _fwd_kernel_onepass)."""
    iq = pl.program_id(2)
    if causal:
        keep = _causal_keep(iq, 0, block_q, block_k, causal_offset, window)
    outs, lses = [], []
    for j in range(hb):                       # static unroll over the group
        jk = j // g                           # local KV head in this block
        q = q_ref[0, :, j * d:(j + 1) * d]            # [bq, d] bf16
        kb = k_ref[0, :, jk * d:(jk + 1) * d]         # [bk, d] bf16
        s = jax.lax.dot_general(
            q, kb, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)       # [bq, bk] f32
        if causal:
            s = jnp.where(keep, s, NEG_INF)
        m = jnp.max(s, axis=1, keepdims=True)
        p = jnp.exp(s - m)
        l = jnp.sum(p, axis=1, keepdims=True)
        vb = v_ref[0, :, jk * d:(jk + 1) * d]
        acc = jax.lax.dot_general(
            p.astype(vb.dtype), vb, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        safe_l = jnp.where(l == 0.0, 1.0, l)
        outs.append((acc / safe_l).astype(o_ref.dtype))
        lses.append(jnp.broadcast_to(m + jnp.log(safe_l), (block_q, 8)))
    o_ref[0] = jnp.concatenate(outs, axis=-1)
    lse_ref[0] = jnp.stack(lses)


def _fwd_kernel_folded(q_ref, k_ref, v_ref, o_ref, lse_ref,
                       acc_ref, m_ref, l_ref, *, causal, block_q, block_k,
                       num_k_blocks, causal_offset, window, hb, g, d):
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    run = _run_predicate(iq, ik, block_q, block_k, causal, causal_offset,
                         window)

    @pl.when(run)
    def _():
        if causal:
            keep = _causal_keep(iq, ik, block_q, block_k, causal_offset,
                                window)
        for j in range(hb):
            jk = j // g
            q = q_ref[0, :, j * d:(j + 1) * d]
            kb = k_ref[0, :, jk * d:(jk + 1) * d]
            s = jax.lax.dot_general(
                q, kb, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            if causal:
                s = jnp.where(keep, s, NEG_INF)
            m_prev = m_ref[j, :, :1]                   # [bq, 1]
            m_cur = jnp.max(s, axis=1, keepdims=True)
            m_new = jnp.maximum(m_prev, m_cur)
            p = jnp.exp(s - m_new)
            corr = jnp.exp(m_prev - m_new)
            l_new = l_ref[j, :, :1] * corr + jnp.sum(p, axis=1, keepdims=True)
            vb = v_ref[0, :, jk * d:(jk + 1) * d]
            acc_ref[j] = acc_ref[j] * corr + jax.lax.dot_general(
                p.astype(vb.dtype), vb, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_ref[j] = jnp.broadcast_to(m_new, m_ref[j].shape)
            l_ref[j] = jnp.broadcast_to(l_new, l_ref[j].shape)

    @pl.when(ik == num_k_blocks - 1)
    def _():
        outs, lses = [], []
        for j in range(hb):
            l = l_ref[j, :, :1]
            safe_l = jnp.where(l == 0.0, 1.0, l)
            outs.append((acc_ref[j] / safe_l).astype(o_ref.dtype))
            lses.append(jnp.broadcast_to(m_ref[j, :, :1] + jnp.log(safe_l),
                                         (block_q, 8)))
        o_ref[0] = jnp.concatenate(outs, axis=-1)
        lse_ref[0] = jnp.stack(lses)


def _fwd_folded(q, k, v, *, h, hkv, causal, block_q, block_k, interpret,
                window=None):
    """q (PRE-SCALED): [B, Sq, H*D]; k/v: [B, Sk, Hkv*D]
    -> (o: [B, Sq, H*D], lse: [B, H, Sq, 8])."""
    b, sq, _ = q.shape
    sk = k.shape[1]
    d = q.shape[-1] // h
    g = h // hkv
    hb = folded_heads_per_block(h, hkv, d)
    kvb = max(1, hb // g)                 # KV heads per grid step
    nq = sq // block_q
    nk = sk // block_k

    # hb == 1 (d % 128 == 0): the KV block is one head, indexed hp // g;
    # hb == m*g: the group's KV heads are exactly block hp of kvb heads.
    if hb == 1:
        idx_k = lambda b_, hp, iq, *r: (b_, (iq, *r)[-1], hp // g)
    else:
        idx_k = lambda b_, hp, iq, *r: (b_, (iq, *r)[-1], hp)

    if nk == 1:
        kernel = functools.partial(
            _fwd_kernel_folded_onepass, causal=causal, block_q=block_q,
            block_k=block_k, causal_offset=sk - sq, window=window,
            hb=hb, g=g, d=d)
        grid = (b, h // hb, nq)
        idx_q = lambda b_, hp, iq: (b_, iq, hp)
        idx_kv = lambda b_, hp, iq: idx_k(b_, hp, iq, 0)
        idx_l = lambda b_, hp, iq: (b_, hp, iq, 0)
        scratch = []
    else:
        kernel = functools.partial(
            _fwd_kernel_folded, causal=causal, block_q=block_q,
            block_k=block_k, num_k_blocks=nk, causal_offset=sk - sq,
            window=window, hb=hb, g=g, d=d)
        grid = (b, h // hb, nq, nk)
        idx_q = lambda b_, hp, iq, ik: (b_, iq, hp)
        idx_kv = lambda b_, hp, iq, ik: idx_k(b_, hp, iq, ik)
        idx_l = lambda b_, hp, iq, ik: (b_, hp, iq, 0)
        scratch = [
            pltpu.VMEM((hb, block_q, d), jnp.float32),
            pltpu.VMEM((hb, block_q, 128), jnp.float32),
            pltpu.VMEM((hb, block_q, 128), jnp.float32),
        ]
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, hb * d), idx_q),
            pl.BlockSpec((1, block_k, kvb * d), idx_kv),
            pl.BlockSpec((1, block_k, kvb * d), idx_kv),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, hb * d), idx_q),
            pl.BlockSpec((1, hb, block_q, 8), idx_l),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, sq, h * d), q.dtype),
            jax.ShapeDtypeStruct((b, h, sq, 8), jnp.float32),
        ],
        scratch_shapes=scratch,
        interpret=interpret,
        **kernel_names(kernel),
    )(q, k, v)


def _bwd_dq_kernel_folded(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                          dq_ref, dq_acc, *, scale, causal, block_q,
                          block_k, num_k_blocks, causal_offset, window,
                          hb, g, d):
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    run = _run_predicate(iq, ik, block_q, block_k, causal, causal_offset,
                         window)

    @pl.when(run)
    def _():
        if causal:
            keep = _causal_keep(iq, ik, block_q, block_k, causal_offset,
                                window)
        for j in range(hb):
            jk = j // g
            q = q_ref[0, :, j * d:(j + 1) * d]
            kb = k_ref[0, :, jk * d:(jk + 1) * d]
            vb = v_ref[0, :, jk * d:(jk + 1) * d]
            do = do_ref[0, :, j * d:(j + 1) * d]
            lse = lse_ref[0, j][:, :1]
            delta = delta_ref[0, j][:, :1]
            s = jax.lax.dot_general(q, kb, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            if causal:
                s = jnp.where(keep, s, NEG_INF)
            p = jnp.exp(s - lse)
            dp = jax.lax.dot_general(do, vb, (((1,), (1,)), ((), ())),
                                     preferred_element_type=jnp.float32)
            ds = (p * (dp - delta)).astype(kb.dtype)
            dq_acc[j] = dq_acc[j] + jax.lax.dot_general(
                ds, kb, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

    @pl.when(ik == num_k_blocks - 1)
    def _():
        dq_ref[0] = jnp.concatenate(
            [(dq_acc[j] * scale).astype(dq_ref.dtype) for j in range(hb)],
            axis=-1)


def _bwd_dkv_kernel_folded(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                           dk_ref, dv_ref, dk_acc, dv_acc, *, causal,
                           block_q, block_k, num_q_blocks, causal_offset,
                           window, hb, g, d):
    ik = pl.program_id(2)
    iq = pl.program_id(3)

    @pl.when(iq == 0)
    def _():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    run = _run_predicate(iq, ik, block_q, block_k, causal, causal_offset,
                         window)

    @pl.when(run)
    def _():
        if causal:
            keep = _causal_keep(iq, ik, block_q, block_k, causal_offset,
                                window)
        for j in range(hb):
            jk = j // g
            q = q_ref[0, :, j * d:(j + 1) * d]
            kb = k_ref[0, :, jk * d:(jk + 1) * d]
            vb = v_ref[0, :, jk * d:(jk + 1) * d]
            do = do_ref[0, :, j * d:(j + 1) * d]
            lse = lse_ref[0, j][:, :1]
            delta = delta_ref[0, j][:, :1]
            s = jax.lax.dot_general(q, kb, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            if causal:
                s = jnp.where(keep, s, NEG_INF)
            p = jnp.exp(s - lse)
            pb = p.astype(do.dtype)
            dv_acc[j] = dv_acc[j] + jax.lax.dot_general(
                pb, do, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            dp = jax.lax.dot_general(do, vb, (((1,), (1,)), ((), ())),
                                     preferred_element_type=jnp.float32)
            ds = (p * (dp - delta)).astype(q.dtype)
            dk_acc[j] = dk_acc[j] + jax.lax.dot_general(
                ds, q, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

    @pl.when(iq == num_q_blocks - 1)
    def _():
        dk_ref[0] = jnp.concatenate(
            [dk_acc[j].astype(dk_ref.dtype) for j in range(hb)], axis=-1)
        dv_ref[0] = jnp.concatenate(
            [dv_acc[j].astype(dv_ref.dtype) for j in range(hb)], axis=-1)


def _bwd_folded(res, grads, *, h, hkv, scale, causal, block_q, block_k,
                interpret, window=None):
    q, k, v, o, lse = res  # q is the PRE-SCALED folded query
    do = grads[0]
    b, sq, _ = q.shape
    sk = k.shape[1]
    d = q.shape[-1] // h
    g = h // hkv
    hb = folded_heads_per_block(h, hkv, d)
    kvb = max(1, hb // g)
    nq = sq // block_q
    nk = sk // block_k

    # delta_i = rowsum(dO_i * O_i), head-major like lse. The [B,Sq,H]
    # transpose is fp32 and tiny (b*s*h words — ~0.4 MB on the honest
    # geometry), nothing like the [B,S,H,D] transposes this path removes.
    delta = (do.astype(jnp.float32) * o.astype(jnp.float32)) \
        .reshape(b, sq, h, d).sum(axis=-1).transpose(0, 2, 1)
    delta = jnp.broadcast_to(delta[..., None], (b, h, sq, 8))

    if hb == 1:
        idx_k = lambda b_, hp, _i, last: (b_, last, hp // g)
    else:
        idx_k = lambda b_, hp, _i, last: (b_, last, hp)

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel_folded, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k, num_k_blocks=nk,
                          causal_offset=sk - sq, window=window,
                          hb=hb, g=g, d=d),
        grid=(b, h // hb, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, hb * d),
                         lambda b_, hp, iq, ik: (b_, iq, hp)),
            pl.BlockSpec((1, block_k, kvb * d),
                         lambda b_, hp, iq, ik: idx_k(b_, hp, iq, ik)),
            pl.BlockSpec((1, block_k, kvb * d),
                         lambda b_, hp, iq, ik: idx_k(b_, hp, iq, ik)),
            pl.BlockSpec((1, block_q, hb * d),
                         lambda b_, hp, iq, ik: (b_, iq, hp)),
            pl.BlockSpec((1, hb, block_q, 8),
                         lambda b_, hp, iq, ik: (b_, hp, iq, 0)),
            pl.BlockSpec((1, hb, block_q, 8),
                         lambda b_, hp, iq, ik: (b_, hp, iq, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, hb * d),
                               lambda b_, hp, iq, ik: (b_, iq, hp)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((hb, block_q, d), jnp.float32)],
        interpret=interpret,
        **kernel_names(_bwd_dq_kernel_folded),
    )(q, k, v, do, lse, delta)

    # dK/dV per q-head (folded [B, Sk, H*D]), then sum each GQA group
    dk_h, dv_h = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel_folded, causal=causal,
                          block_q=block_q, block_k=block_k, num_q_blocks=nq,
                          causal_offset=sk - sq, window=window,
                          hb=hb, g=g, d=d),
        grid=(b, h // hb, nk, nq),
        in_specs=[
            pl.BlockSpec((1, block_q, hb * d),
                         lambda b_, hp, ik, iq: (b_, iq, hp)),
            pl.BlockSpec((1, block_k, kvb * d),
                         lambda b_, hp, ik, iq: idx_k(b_, hp, iq, ik)),
            pl.BlockSpec((1, block_k, kvb * d),
                         lambda b_, hp, ik, iq: idx_k(b_, hp, iq, ik)),
            pl.BlockSpec((1, block_q, hb * d),
                         lambda b_, hp, ik, iq: (b_, iq, hp)),
            pl.BlockSpec((1, hb, block_q, 8),
                         lambda b_, hp, ik, iq: (b_, hp, iq, 0)),
            pl.BlockSpec((1, hb, block_q, 8),
                         lambda b_, hp, ik, iq: (b_, hp, iq, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_k, hb * d),
                         lambda b_, hp, ik, iq: (b_, ik, hp)),
            pl.BlockSpec((1, block_k, hb * d),
                         lambda b_, hp, ik, iq: (b_, ik, hp)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, sk, h * d), k.dtype),
            jax.ShapeDtypeStruct((b, sk, h * d), v.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((hb, block_k, d), jnp.float32),
                        pltpu.VMEM((hb, block_k, d), jnp.float32)],
        interpret=interpret,
        **kernel_names(_bwd_dkv_kernel_folded),
    )(q, k, v, do, lse, delta)

    if g > 1:
        dk = dk_h.reshape(b, sk, hkv, g, d).sum(axis=3).reshape(b, sk, -1)
        dv = dv_h.reshape(b, sk, hkv, g, d).sum(axis=3).reshape(b, sk, -1)
    else:
        dk, dv = dk_h, dv_h
    return dq, dk.astype(k.dtype), dv.astype(v.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=tuple(range(3, 11)))
def _flash_folded(q, k, v, h, hkv, scale, causal, block_q, block_k,
                  interpret, window):
    qs = (q.astype(jnp.float32) * scale).astype(q.dtype)
    o, _ = _fwd_folded(qs, k, v, h=h, hkv=hkv, causal=causal,
                       block_q=block_q, block_k=block_k,
                       interpret=interpret, window=window)
    return o


def _flash_folded_fwd(q, k, v, h, hkv, scale, causal, block_q, block_k,
                      interpret, window):
    qs = (q.astype(jnp.float32) * scale).astype(q.dtype)
    o, lse = _fwd_folded(qs, k, v, h=h, hkv=hkv, causal=causal,
                         block_q=block_q, block_k=block_k,
                         interpret=interpret, window=window)
    return o, (qs, k, v, o, lse)


def _flash_folded_bwd(h, hkv, scale, causal, block_q, block_k, interpret,
                      window, res, g):
    return _bwd_folded(res, (g,), h=h, hkv=hkv, scale=scale, causal=causal,
                       block_q=block_q, block_k=block_k,
                       interpret=interpret, window=window)


_flash_folded.defvjp(_flash_folded_fwd, _flash_folded_bwd)


def flash_attention_folded(q, k, v, *, num_heads: int,
                           num_kv_heads: Optional[int] = None,
                           causal: bool = True,
                           mask: Optional[jax.Array] = None,
                           scale: Optional[float] = None,
                           window: Optional[int] = None,
                           block_q: Optional[int] = None,
                           block_k: Optional[int] = None,
                           interpret: Optional[bool] = None):
    """Layout-native flash attention. q: [B,Sq,H*D]; k/v: [B,Sk,Hkv*D];
    returns [B,Sq,H*D] — no [B,S,H,D] round-trip on either the forward
    or the ``custom_vjp`` backward.

    Semantics (causal / sliding ``window`` / GQA / ``scale``) match
    :func:`flash_attention` exactly; only the array layout differs.
    """
    if mask is not None:
        raise NotImplementedError(
            "flash_attention_folded supports causal/full (+sliding window) "
            "only; use ops.attention.dot_product_attention for custom masks")
    if window is not None and not causal:
        raise ValueError("sliding window requires causal attention")
    if window is not None and window <= 0:
        raise ValueError(f"window must be positive, got {window}")
    hkv = num_kv_heads if num_kv_heads is not None else num_heads
    if q.ndim != 3 or k.ndim != 3 or v.ndim != 3:
        raise ValueError("folded layout expects rank-3 [B, S, H*D] inputs")
    b, sq, hd = q.shape
    _, sk, kvd = k.shape
    if num_heads % hkv:
        raise ValueError(f"GQA needs H % Hkv == 0, got {num_heads} % {hkv}")
    if hd % num_heads or kvd % hkv:
        raise ValueError(
            f"folded widths ({hd}, {kvd}) must be divisible by their head "
            f"counts ({num_heads}, {hkv})")
    d = hd // num_heads
    if kvd // hkv != d:
        raise ValueError(
            f"q head_dim {d} != kv head_dim {kvd // hkv}")
    if folded_heads_per_block(num_heads, hkv, d) is None:
        raise ValueError(
            f"no lane-aligned head grouping for H={num_heads} Hkv={hkv} "
            f"d={d}; use the [B,S,H,D] flash_attention path")
    scale = scale if scale is not None else 1.0 / (d ** 0.5)
    block_q = block_q or _pick_block(sq, DEFAULT_BLOCK_Q)
    block_k = block_k or _pick_block(sk, DEFAULT_BLOCK_K)
    if sq % block_q or sk % block_k:
        raise ValueError(
            f"seq lengths ({sq},{sk}) must divide blocks ({block_q},{block_k})")
    if interpret is None:
        interpret = not on_tpu()

    def kernel(q, k, v, head_shards):
        return _flash_folded(
            q, k, v, int(num_heads) // head_shards, int(hkv) // head_shards,
            float(scale), bool(causal), int(block_q), int(block_k),
            bool(interpret), int(window) if window is not None else None)

    return run_partitioned(
        kernel, q, k, v, None if interpret else mesh_partition(
            b, num_heads, hkv, lambda n: folded_heads_per_block(
                num_heads // n, hkv // n, d) is not None))


# ===================================================================== #
# dslint contract-checker registration (see analysis/pallas_lint.py):
# the kernel_selftest parameter grid, invoked under the checker's
# capture context — no kernel body runs, nothing compiles.
# ===================================================================== #
from deepspeed_tpu.analysis.registry import pallas_kernel_case  # noqa: E402


def _dslint_qkv(h, hkv, d, s=512, b=2, dtype=jnp.bfloat16):
    import numpy as np

    rng = np.random.default_rng(0)
    mk = lambda heads: jnp.asarray(
        rng.standard_normal((b, s, heads, d)).astype(np.float32), dtype)
    return mk(h), mk(hkv), mk(hkv)


@pallas_kernel_case(
    "flash_attention",
    note="selftest grid (MHA d64 / GQA d128 / SWA) + multi-k fwd and "
         "both backward kernels at 128x128 blocks")
def _dslint_flash_cases():
    for h, hkv, d, win in ((8, 8, 64, None), (8, 2, 128, None),
                           (4, 4, 64, 256)):
        q, k, v = _dslint_qkv(h, hkv, d)
        flash_attention(q, k, v, causal=True, window=win, interpret=True)
    h, hkv, d, bq, bk = 4, 2, 64, 128, 128
    q, k, v = _dslint_qkv(h, hkv, d)
    qt, kt, vt = (x.transpose(0, 2, 1, 3) for x in (q, k, v))
    o, lse = _fwd(qt, kt, vt, causal=True, block_q=bq, block_k=bk,
                  interpret=True)
    _bwd((qt, kt, vt, o, lse), (o,), scale=0.125, causal=True,
         block_q=bq, block_k=bk, interpret=True)


@pallas_kernel_case(
    "flash_attention_folded",
    note="folded [B,S,H*D] lane layout: the d=64 head-group lane "
         "slicing (pairs, a GQA group of 2), d=32 quads, SWA")
def _dslint_flash_folded_cases():
    for h, hkv, d, win in ((12, 12, 64, None), (8, 4, 64, None),
                           (4, 4, 32, None), (4, 4, 64, 256)):
        q, k, v = _dslint_qkv(h, hkv, d)
        b, s = q.shape[:2]
        flash_attention_folded(
            q.reshape(b, s, h * d), k.reshape(b, s, hkv * d),
            v.reshape(b, s, hkv * d), num_heads=h, num_kv_heads=hkv,
            causal=True, window=win, interpret=True)
    h, hkv, d, bq, bk = 4, 2, 64, 128, 128
    q, k, v = _dslint_qkv(h, hkv, d)
    b, s = q.shape[:2]
    qf = q.reshape(b, s, h * d)
    kf = k.reshape(b, s, hkv * d)
    vf = v.reshape(b, s, hkv * d)
    o, lse = _fwd_folded(qf, kf, vf, h=h, hkv=hkv, causal=True,
                         block_q=bq, block_k=bk, interpret=True)
    _bwd_folded((qf, kf, vf, o, lse), (o,), h=h, hkv=hkv, scale=0.125,
                causal=True, block_q=bq, block_k=bk, interpret=True)
