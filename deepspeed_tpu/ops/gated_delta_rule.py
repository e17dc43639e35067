"""Gated delta rule — the recurrence of a Gated DeltaNet (linear-attention)
layer over a pool of per-sequence state slots.

Per value head the layer keeps a matrix ``S [dk, dv]`` (float32) for every
live sequence.  One token ``t`` with query ``q_t`` and key ``k_t`` (both
L2-normalised, ``q`` scaled), value ``v_t``, log-decay ``g_t <= 0`` and
write strength ``beta_t`` in (0, 2) does (``sigmoid(b)`` in (0, 1) as
Qwen3-Next has it; ``2 sigmoid(b)`` where a model allows negative
eigenvalues, Olmo-Hybrid's ``linear_allow_neg_eigval``: along ``k`` the
state's transition ``I - beta k k^T`` then reaches -1)::

    S *= exp(g_t);  d = (v_t - S^T k_t) * beta_t;  S += k_t d^T;  o_t = S^T q_t

The states live in a slot pool owned by the serving engine's state manager
(``inference/v2/ragged/state_pool.py``); the last slot is scratch, where pad
rows write.  **The pool's layout is one rule, by the widths alone**
(:func:`state_leaf_shape`; the model's ``state`` leaf asks it): where one
head's values do not fill whole 128-lane tiles and two heads' do (``dv %
128 != 0``, ``2 dv % 128 == 0``, H even: Olmo-Hybrid's 30 x 96 x 192), a
sequence's matrices are stored as head PAIRS, ``[slots + 1, H / 2, dk, 2
dv]``, lanes below ``dv`` the even head; otherwise (``dv = 128``:
Qwen3-Next; tiny test widths such as 48) ``[slots + 1, H, dk, dv]``.  Both
entry points recognise the layout from ``pool.shape`` against the rows'
(:func:`_paired`) and hand it to the SAME kernel as a static argument; the
compositions unpair on the way in and pair on the way out.  Two entry
points, one for each segment of a ragged batch
(``RaggedBatchWrapper.set_alignment``):

* :func:`gdn_step` — rows of one token each (a decode step, the
  single-token segment): one read and one write of each row's slot.
* :func:`gdn_chunk` — the tile segment: every ``tile`` rows belong to one
  sequence, tiles of one sequence follow each other in position order.  The
  recurrence is computed in its chunked (WY) form, ``chunk`` tokens at a
  time: inside a chunk the ``chunk x chunk`` unit-lower-triangular system is
  inverted by block doubling (exact block forward substitution, ``-T22 a21
  T11`` level by level) and the state enters through three matmuls a chunk.

  The kernel does a grid step's ``hb`` heads x ``tile // chunk`` chunks in
  two phases, every product a 3-D ``dot_general`` over a batch of
  chunk-heads: a float32 product is six bf16 passes of its left operand's
  rows through the MXU, and passes of DIFFERENT chunk-heads issued one
  after another keep all of the chip's MXUs fed where one chunk-head's own
  chain of products does not (chip, PR 55: 1.85 x a call at the same
  products).  **Phase A**, state-free, over all chunk-heads of the step:
  ``[k beta; q] k^T`` (one product), the inverse ``T``.  **Phase B**, the
  chunks of the tile in order, the ``hb`` heads of a chunk as the batch:
  ``[k beta exp G; q exp G] S`` (one product), ``v_new = T (v beta - .)``,
  ``o = . + attn v_new``, ``S = S exp(G_last) + (k exp(G_last - G))^T
  v_new``.  The inverse multiplies what is live only
  (:func:`_tri_inverse_live`): the 16-row diagonal blocks side by side as a
  ``[16, chunk]`` left operand (four levels of two 16-row products, the
  last of them the merge 16 -> 32), then the merge 32 -> 64 on the lower
  half's rows; 192 rows pushed a chunk where :func:`_tri_inverse`, which
  the composition keeps, pushes 768.

Each has a Mosaic kernel (the TPU path; ``interpret=True`` in tests) and an
XLA composition of the same mathematics (``*_reference``: the path off the
TPU and the parity oracle, as ``gmm_reference`` is for the grouped GEMM).
Pad rows carry ``g = 0`` and ``beta = 0`` (the caller masks them), which
leaves a state exactly as it was; ``reset`` zeroes a slot before its first
token (a sequence whose first position is 0).  Everything is float32.

**Shapes the kernels have run at on a chip** (v5e): 32 heads of ``dk = dv =
128`` (Qwen3-Next, since PR 29; PR 55's chunk kernel) and 30 heads of ``dk =
96``, ``dv = 192`` (Olmo-Hybrid, PR 56), both through the one ``gdn_step``
and the one ``gdn_chunk``.  A head group (``_head_block``) is the largest
divisor of H up to 8 (step) or 4 (chunk): 8 and 4 of 32 heads, 6 and 3 of
30; the step kernel's row blocks hold a group as whole trailing axes of 4-D
arrays, so a group need be no multiple of 8.  With ``beta`` up to 2 the
chunk form's unit-lower system has off-diagonal entries up to 2 in
magnitude; block doubling holds a float64 solve to rounding there
(``tests/unit/test_gdn_chunk_kernel.py``).

**Why pairs.**  A ``dv`` of 192 is one and a half lane tiles: stored ``[30,
96, 192]`` the chip held each float32 state row as 256 lanes, a third more
bytes in HBM and in every decode step's read and write than the
mathematics has (that is history since PR 58; the step kernel read 1,179 us
a 128-row call so, 59.6% of what its bytes need).  Two heads side by side
are three whole tiles.  The step kernel on a pair is the same VPU update,
a pair's ``q`` / ``k`` column chosen by lane half.  The chunk kernel on a
pair moves nothing across a tile boundary either: see
:func:`_gdn_chunk_kernel`.  The paired form adds exact zeros inside float32
products and sums them in another order, nothing else.  One thing it does
change, as ``modules/conv.py``'s one-hot form did for the tails.  An
output lane of a product sums its own lane's column of the RIGHT operand,
so a non-finite value in a head's state or values (the right operands: the
state, ``v beta - .``, ``v_new``) stays in that head's lanes as before,
and the half of ``rows x state`` a head does not want is dropped by a
select, not a product.  But the LEFT operands of the block-diagonal
products hold a pair side by side (``T``, the intra-chunk attention, ``k
exp(G_last - G)``; inside the joint inverse, the two heads' diagonal
blocks), and a NON-FINITE value there meets the other head's zero block
(``inf x 0``) and reaches the pair's other head, where in the natural
layout it stays in its head.  Those operands are functions of a head's
keys, write strengths and decays, a layer's own finite inputs; it is
never another pair or another sequence.
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

#: values of one lane tile: an array's minor dimension is stored in whole
#: tiles of this many
_LANES = 128


def state_leaf_shape(h: int, dk: int, dv: int):
    """THE rule of the state's layout: what one sequence's matrices of one
    layer are stored as.  Where one head's values do not fill whole lane
    tiles and two heads' do (30 x 96 x 192 -> ``(15, 96, 384)``), head
    PAIRS ``(h / 2, dk, 2 dv)``, lanes below ``dv`` the even head;
    otherwise ``(h, dk, dv)`` (``dv = 128``; widths no pair fills either,
    ``dv = 48``; an odd head count)."""
    if dv % _LANES and not (2 * dv) % _LANES and not h % 2:
        return (h // 2, dk, 2 * dv)
    return (h, dk, dv)


def _paired(pool, q, v) -> bool:
    """Whether ``pool`` holds head pairs, from its shape against the rows'
    (``q [., H, dk]``, ``v [., H, dv]``)."""
    h, dk, dv = q.shape[1], q.shape[2], v.shape[-1]
    if pool.shape[1:] == (h, dk, dv):
        return False
    if pool.shape[1:] != (h // 2, dk, 2 * dv) or h % 2:
        raise ValueError(
            f"a state pool {pool.shape} is neither [slots, {h}, {dk}, {dv}] "
            f"nor its pairs [slots, {h // 2}, {dk}, {2 * dv}]")
    return True


def _pairs(pool):
    """``[N, H, dk, dv] -> [N, H / 2, dk, 2 dv]``."""
    n, h, dk, dv = pool.shape
    return jnp.moveaxis(pool.reshape(n, h // 2, 2, dk, dv), 2, 3).reshape(
        n, h // 2, dk, 2 * dv)


def _unpairs(pool):
    """``[N, H / 2, dk, 2 dv] -> [N, H, dk, dv]``."""
    n, hp, dk, dv2 = pool.shape
    return jnp.moveaxis(pool.reshape(n, hp, dk, 2, dv2 // 2), 3, 2).reshape(
        n, 2 * hp, dk, dv2 // 2)


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
    ``(o [S, H, dv], new pool)``.  A pool of head pairs
    (:func:`state_leaf_shape`) is unpaired on the way in and paired on the
    way out: the mathematics knows one layout."""
    if _paired(pool, q, v):
        o, pool = gdn_step_reference(_unpairs(pool), q, k, v, g, beta,
                                     slots, reset)
        return o, _pairs(pool)
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
    back, so the carry from tile to tile goes through the pool.  A pool of
    head pairs: as :func:`gdn_step_reference`."""
    if _paired(pool, q, v):
        o, pool = gdn_chunk_reference(_unpairs(pool), q, k, v, g, beta,
                                      tile_slot, tile_reset, tile, chunk)
        return o, _pairs(pool)
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
                     b_ref, s_in_ref, o_ref, s_out_ref, *, hb: int,
                     pair: bool = False):
    """Grid (rows, head groups).  All on the VPU: with ``k`` as a column
    and ``v``, ``d``, ``o`` as rows, ``S^T k`` is a sublane reduction and
    ``k d^T`` a broadcast product, so no float32 pass goes through the
    MXU.  ``o = S_new^T q = a S0^T q + (q . k) d``.

    ``pair``: the group's ``hb`` entries are head PAIRS, rows and state
    ``2 dv`` lanes wide, and a pair's ``q`` / ``k`` column is the even
    head's below lane ``dv`` and the odd head's from there on."""
    s = pl.program_id(0)
    keep = jnp.where(reset_ref[s] != 0, 0.0, 1.0).astype(F32)
    if pair:
        lanes = s_in_ref.shape[-1]
        even = jax.lax.broadcasted_iota(jnp.int32, (1, lanes), 1) \
            < lanes // 2

    def col(ref, j):                # [dk, 1], or [dk, 2 dv] by lane half
        if pair:
            return jnp.where(even, ref[0, 0, :, 2 * j:2 * j + 1],
                             ref[0, 0, :, 2 * j + 1:2 * j + 2])
        return ref[0, 0, :, j:j + 1]

    for j in range(hb):
        s0 = s_in_ref[0, j] * keep                        # [dk, dv]
        qc = col(qt_ref, j)                               # [dk, 1]
        kc = col(kt_ref, j)
        a = a_ref[0, 0, j:j + 1, :]                       # [1, dv]
        ks = jnp.sum(kc * s0, axis=0, keepdims=True)      # [1, dv]
        qs = jnp.sum(qc * s0, axis=0, keepdims=True)
        qk = jnp.sum(qc * kc, axis=0, keepdims=True)      # [1, 1]
        d = b_ref[0, 0, j:j + 1, :] * (v_ref[0, 0, j:j + 1, :] - a * ks)
        s_out_ref[0, j] = a * s0 + kc * d
        o_ref[0, 0, j:j + 1, :] = a * qs + qk * d


@functools.partial(jax.jit, static_argnames=("hb", "interpret"))
def _gdn_step_call(pool, q, k, v, g, beta, slots, reset, hb: int,
                   interpret: bool):
    """``hb``: entries of the pool's head axis a grid step holds (heads,
    or pairs of a paired pool)."""
    s, h, dk = q.shape
    dv = v.shape[-1]
    pair = _paired(pool, q, v)
    hg = pool.shape[1] // hb
    per = 2 if pair else 1          # heads an entry of the group holds

    def cols(x):                    # [S, H, dk] -> [S, hg, dk, hb]
        return jnp.swapaxes(x.reshape(s, hg, per * hb, dk), 2, 3)

    def rows(x):                    # [S, H] or [S, H, dv] -> [S, hg, hb, dv]
        if x.ndim == 2:
            x = jnp.broadcast_to(x[..., None], (s, h, dv))
        return x.reshape(s, hg, hb, per * dv)

    kernel = functools.partial(_gdn_step_kernel, hb=hb, pair=pair)
    # a head group's rows and columns are whole trailing axes of their
    # arrays, so ``hb`` is any divisor of H (30 heads: no multiple of 8)
    col_spec = pl.BlockSpec((1, 1, dk, per * hb),
                            lambda i, j, sl, rs: (i, j, 0, 0))
    row_spec = pl.BlockSpec((1, 1, hb, per * dv),
                            lambda i, j, sl, rs: (i, j, 0, 0))
    pool_spec = pl.BlockSpec((1, hb) + pool.shape[2:],
                             lambda i, j, sl, rs: (sl[i], j, 0, 0))
    o, pool = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(s, hg),
            in_specs=[col_spec, col_spec, row_spec, row_spec, row_spec,
                      pool_spec],
            out_specs=[row_spec, pool_spec]),
        out_shape=[jax.ShapeDtypeStruct((s, hg, hb, per * dv), F32),
                   jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
        # the pool is updated in place: operand 7 (after the two scalar
        # vectors and five row operands) is output 1
        input_output_aliases={7: 1},
        interpret=interpret,
        **kernel_names(kernel),
    )(slots.astype(jnp.int32), reset.astype(jnp.int32), cols(q), cols(k),
      rows(v), rows(jnp.exp(g)), rows(beta), pool)
    return o.reshape(s, h, dv), pool


# --------------------------------------------------------------------- #
# Mosaic kernel (a): the chunked rule over the tile segment
# --------------------------------------------------------------------- #
def _mm(a, b, lhs: int = -1, rhs: int = -2):
    """``a @ b`` over the last two axes at float32 passes, leading axes
    batch; ``rhs=-1`` is ``a @ b^T`` and ``lhs=-2`` is ``a^T @ b``."""
    n = a.ndim
    batch = tuple(range(n - 2))
    return jax.lax.dot_general(a, b, (((n + lhs,), (n + rhs,)),
                                      (batch, batch)), precision=_HI,
                               preferred_element_type=F32)


#: rows of the diagonal blocks :func:`_tri_inverse_live` inverts side by side
_DIAG = 16


def _tri_inverse_live(a, live: Optional[int] = None):
    """``(I + a)^-1`` as :func:`_tri_inverse` forms it (block doubling,
    ``-T22 a21 T11``), multiplying only what is live.  ``a [..., C, C]``
    strictly lower, C a power of two.  ``live``: ``a`` is block diagonal in
    blocks of that many rows (several systems in one array), so the levels
    that would merge them, whose ``a21`` is zero, are not made.

    While the blocks being merged have at most ``_DIAG`` rows the inverse's
    diagonal blocks of ``_DIAG`` rows are kept SIDE BY SIDE, ``s [..., 16,
    C]`` with ``s[r, 16 b + c] = T[16 b + r, 16 b + c]``: ``(s m) F``, with
    ``m`` the level's ``a21`` blocks and ``F`` the block-diagonal form of
    ``s`` (``s`` stacked and masked), is ``T22 a21 T11`` of every pair of
    blocks from two products of 16 rows where the full form multiplies 64.
    Below 16 rows that stays inside ``s`` (``s - (s m) F``); the first
    level needs no product (``I - m``); the level that merges 16-row blocks
    lands below the diagonal blocks of the full form.  From 32 rows up a
    level changes the odd blocks' rows only: those rows alone go through
    ``(T_odd m) T``."""
    c = a.shape[-1]
    d = min(_DIAG, c)
    i = jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
    j = jax.lax.broadcasted_iota(jnp.int32, (c, c), 1)

    def low(b):                 # where a level of size b has its a21 blocks
        return ((i // (2 * b)) == (j // (2 * b))) & ((i // b) % 2 == 1) \
            & ((j // b) % 2 == 0)

    def stacked(s):             # [.., d, C] -> [.., C, C], s in every slab
        return jnp.concatenate([s] * (c // d), axis=-2)

    def pairs(s, f, b):         # T22 a21 T11 of the level, side by side
        return _mm(_mm(s, jnp.where(low(b), a, 0.0)), f)

    # level 1, (I - m), folded to the side-by-side form: each slab of d rows
    # is live in its own d columns only
    t = (i == j).astype(F32) - jnp.where(low(1), a, 0.0)
    s = sum(t[..., u:u + d, :] for u in range(0, c, d))
    diag = (i // d) == (j // d)
    b = 2
    while b < d:
        s = s - pairs(s, jnp.where(diag, stacked(s), 0.0), b)
        b *= 2
    t = jnp.where(diag, stacked(s), 0.0)
    top = c if live is None else live
    if b < top:
        t = t - jnp.where(low(b), stacked(pairs(s, t, b)), 0.0)
        b *= 2
    while b < top:
        odd = [t[..., u:u + b, :] for u in range(b, c, 2 * b)]
        y = _mm(_mm(jnp.concatenate(odd, axis=-2),
                    jnp.where(low(b), a, 0.0)), t)
        rows = []
        for n, u in enumerate(range(0, c, 2 * b)):
            rows += [t[..., u:u + b, :], odd[n] - y[..., n * b:(n + 1) * b, :]]
        t = jnp.concatenate(rows, axis=-2)
        b *= 2
    return t


def _gdn_chunk_kernel(slot_ref, reset_ref, q_ref, k_ref, v_ref, c_ref, d_ref,
                      s_in_ref, o_ref, s_out_ref, *, hb: int, tile: int,
                      chunk: int, pair: bool = False):
    """Grid (head groups, tiles), tiles innermost: the tiles of one
    sequence follow each other and map to the same block of the pool, so
    Pallas neither fetches the slot again nor writes it back between them
    — the state is carried in the output block, read from the pool at a
    sequence's first tile and written once when the slot changes.

    A grid step holds ``hb x (tile // chunk)`` chunk-heads.  Every product
    is a 3-D ``dot_general`` over a batch of them, so that the MXU passes
    of one chunk-head follow those of the next and not its own earlier
    ones.  Phase A, over all chunk-heads of the step (chunk-major): ``[k
    beta; q] k^T`` in one product and the inverse ``T``.  Phase B, chunk
    by chunk over the ``hb`` heads: ``[k beta exp G; q exp G] S`` in one
    product, ``v_new = T (v beta - .)``, ``o``, and the state's update.

    ``pair``: the group's ``hb`` entries are head PAIRS and nothing leaves
    its lane tile.  What has ``dv`` in it (``v``, ``o``, the state) holds a
    pair side by side on the lanes, ``2 dv`` wide; so do the ``chunk``-wide
    matrices (the decays, ``T``, the intra-chunk attention: two 64-wide
    heads fill the 128 lanes one wasted half of).  What is ``dk`` wide
    (``q``, ``k``, the per-row scalars; refs ``[hb, 2, tile, .]``) holds
    the pair's rows one head UNDER the other (:func:`stack`).  A product
    with such rows on the left gives both heads' rows against both heads'
    lanes, and each head keeps its own half (:func:`halves`); a product
    that contracts over a chunk's rows takes the side-by-side matrix on
    the left and the pair's values as a block-diagonal right operand
    (:func:`diag2`: ``[T_0 | T_1] [[x_0, 0], [0, x_1]] = [T_0 x_0 | T_1
    x_1]``), the zeros exact.  The two heads' systems are inverted as ONE
    block-diagonal ``[2 chunk, 2 chunk]`` system, its last level skipped
    (:func:`_tri_inverse_live`, ``live``), and folded side by side."""
    t = pl.program_id(1)
    first = jnp.logical_or(t == 0,
                           slot_ref[jnp.maximum(t - 1, 0)] != slot_ref[t])

    @pl.when(first)
    def _():
        keep = jnp.where(reset_ref[t] != 0, 0.0, 1.0).astype(F32)
        s_out_ref[...] = s_in_ref[...] * keep

    dk, dv = k_ref.shape[-1], v_ref.shape[-1]       # dv: the state's lanes
    live = 2 * chunk if pair else chunk             # rows a stack holds
    i = jax.lax.broadcasted_iota(jnp.int32, (live, live), 0)
    j = jax.lax.broadcasted_iota(jnp.int32, (live, live), 1)
    spans = [pl.ds(c * chunk, chunk) for c in range(tile // chunk)]

    def stack(ref, rows):       # a chunk's rows of the step's hb entries
        if len(ref.shape) == 4:     # [hb, 2, tile, w] -> [hb, 2 C, w]
            return jnp.concatenate([ref[:, 0, rows, :], ref[:, 1, rows, :]],
                                   axis=1)
        return ref[:, rows, :]

    def even(shape):            # the even head's lanes
        return jax.lax.broadcasted_iota(jnp.int32, shape, len(shape) - 1) \
            < shape[-1] // 2

    def halves(x):              # [., 2 C, w] -> [., C, w], each head's own
        if not pair:
            return x
        top, low = x[:, :chunk], x[:, chunk:]
        return jnp.where(even(top.shape), top, low)

    def diag2(x):               # [., C, w] -> [., 2 C, w], block diagonal
        if not pair:
            return x
        mine = even(x.shape)
        return jnp.concatenate([jnp.where(mine, x, 0.0),
                                jnp.where(mine, 0.0, x)], axis=1)

    def held(ref):              # [hb, tile, w] -> [chunks * hb, C, w]
        return jnp.concatenate([stack(ref, rows) for rows in spans], axis=0)

    k, decay = held(k_ref), held(d_ref)     # decay [., C, live], lower
    qk = _mm(jnp.concatenate([k * held(c_ref)[..., 0:1], held(q_ref)],
                             axis=1), k, rhs=-1)
    tm = _tri_inverse_live(jnp.where(i > j, qk[:, :live] * diag2(decay), 0.0),
                           live=chunk)
    if pair:                    # [[T_0, 0], [0, T_1]] -> [T_0 | T_1]
        tm = tm[:, :chunk] + tm[:, chunk:]
    attn = halves(qk[:, live:]) * decay

    for c, rows in enumerate(spans):
        heads = slice(c * hb, (c + 1) * hb)
        st = s_out_ref[0]                                 # [hb, dk, dv]
        k, cols = stack(k_ref, rows), stack(c_ref, rows)  # cols [hb, C, 4]
        beta, eg, kd = cols[..., 0:1], cols[..., 1:2], cols[..., 2:3]
        u = _mm(jnp.concatenate([k * (beta * eg), stack(q_ref, rows) * eg],
                                axis=1), st)              # [hb, 2C, dv]
        if pair:                # each head's write strength on its lanes
            beta = halves(jnp.broadcast_to(beta, (hb, live, dv)))
        v_new = diag2(_mm(tm[heads], diag2(v_ref[:, rows, :] * beta
                                           - halves(u[:, :live]))))
        o_ref[:, rows, :] = halves(u[:, live:]) + _mm(attn[heads], v_new)
        # exp(G_last), the same in every row of the chunk's column: eight
        # rows of it across the lanes, stacked to the state's rows (Mosaic
        # does not broadcast a [1, 1] value both ways at once)
        last = jnp.broadcast_to(cols[:, 0:8, 3:4], (hb, 8, dv))
        if pair:
            last = jnp.where(even(last.shape), last, jnp.broadcast_to(
                cols[:, chunk:chunk + 8, 3:4], (hb, 8, dv)))
        last = jnp.concatenate([last] * (dk // 8), axis=1)
        s_out_ref[0] = st * last + _mm(k * kd, v_new, lhs=-2)


@functools.partial(jax.jit, static_argnames=("tile", "chunk", "hb",
                                             "interpret"))
def _gdn_chunk_call(pool, q, k, v, g, beta, tile_slot, tile_reset,
                    tile: int, chunk: int, hb: int, interpret: bool):
    """``hb``: as :func:`_gdn_step_call` takes it."""
    t_rows, h, dk = q.shape
    dv = v.shape[-1]
    pair = _paired(pool, q, v)
    n, nt = t_rows // chunk, t_rows // tile
    # per-row scalars and the decay mask are elementwise work XLA fuses;
    # the kernel gets matrices only.  Head-major from the start: the small
    # [T, H] arrays are transposed, not the lane-padded columns
    hc = lambda x: jnp.swapaxes(x, 0, 1).reshape(h, n, chunk)
    gc = jnp.cumsum(hc(g), axis=-1)                           # [H, n, C]
    eg = jnp.exp(gc)
    cols = jnp.stack([hc(beta), eg, jnp.exp(gc[..., -1:] - gc),
                      jnp.broadcast_to(eg[..., -1:], eg.shape)],
                     axis=-1).reshape(h, t_rows, 4)
    i = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    j = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    decay = jnp.exp(jnp.where(i >= j, gc[..., :, None] - gc[..., None, :],
                              -jnp.inf)).reshape(h, t_rows, chunk)
    hm = lambda x: jnp.swapaxes(x, 0, 1)                      # head-major
    kernel = functools.partial(_gdn_chunk_kernel, hb=hb, tile=tile,
                               chunk=chunk, pair=pair)

    def rows(width):
        return pl.BlockSpec((hb, tile, width),
                            lambda hg, t, sl, rs: (hg, t, 0))

    def under(width):               # what is dk wide: q, k, the scalars
        if not pair:
            return rows(width)
        return pl.BlockSpec((hb, 2, tile, width),
                            lambda hg, t, sl, rs: (hg, 0, t, 0))

    tile_slot, tile_reset = tile_slot.astype(jnp.int32), \
        tile_reset.astype(jnp.int32)
    q, k, v = hm(q), hm(k), hm(v)
    if pair:                        # see the kernel's docstring
        q, k, cols = (x.reshape((h // 2, 2) + x.shape[1:])
                      for x in (q, k, cols))
        side = lambda x: jnp.moveaxis(
            x.reshape((h // 2, 2) + x.shape[1:]), 1, 2).reshape(
            h // 2, t_rows, 2 * x.shape[-1])
        v, decay = side(v), side(decay)
    pool_spec = pl.BlockSpec((1, hb) + pool.shape[2:],
                             lambda hg, t, sl, rs: (sl[t], hg, 0, 0))
    o, pool = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(pool.shape[1] // hb, nt),
            in_specs=[under(dk), under(dk), rows(v.shape[-1]), under(4),
                      rows(decay.shape[-1]), pool_spec],
            out_specs=[rows(v.shape[-1]), pool_spec]),
        out_shape=[jax.ShapeDtypeStruct(v.shape, F32),
                   jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
        input_output_aliases={7: 1},
        interpret=interpret,
        **kernel_names(kernel),
    )(tile_slot, tile_reset, q, k, v, cols, decay, pool)
    o = hm(o)
    if pair:                        # [T, H / 2, 2 dv]: the heads in order
        o = o.reshape(t_rows, h, dv)
    return o, pool


# --------------------------------------------------------------------- #
# Public entries
# --------------------------------------------------------------------- #
def _head_block(h: int, want: int) -> int:
    """Entries of the pool's head axis a grid step holds: the largest
    divisor of ``h`` up to ``want`` (32 heads: 8 and 4; 15 pairs: 5 and 3;
    30 heads unpaired: 6 and 3)."""
    return max(b for b in range(1, want + 1) if h % b == 0)


def _kernel_mode(interpret: Optional[bool]):
    """(run the kernel, in interpret mode): ``None`` (the served default)
    is the kernel on a TPU and the composition elsewhere."""
    if interpret is None:
        return on_tpu(), False
    return True, bool(interpret)


def gdn_step(pool, q, k, v, g, beta, slots, reset,
             interpret: Optional[bool] = None):
    """One token a row: see :func:`gdn_step_reference` for the shapes.
    ``pool`` as :func:`state_leaf_shape` gives it: either layout is
    recognised from its shape against the rows'."""
    use, interp = _kernel_mode(interpret)
    if not use:
        return gdn_step_reference(pool, q, k, v, g, beta, slots, reset)
    return _gdn_step_call(pool, q, k, v, g, beta, slots, reset,
                          _head_block(pool.shape[1], 8), interp)


def gdn_chunk(pool, q, k, v, g, beta, tile_slot, tile_reset, tile: int,
              interpret: Optional[bool] = None):
    """The tile segment: see :func:`gdn_chunk_reference` for the shapes."""
    use, interp = _kernel_mode(interpret)
    if not use:
        return gdn_chunk_reference(pool, q, k, v, g, beta, tile_slot,
                                   tile_reset, tile)
    return _gdn_chunk_call(pool, q, k, v, g, beta, tile_slot, tile_reset,
                           tile, min(CHUNK, tile),
                           _head_block(pool.shape[1], 4), interp)


# --------------------------------------------------------------------- #
# dslint contract-checker registration (see analysis/pallas_lint.py): both
# kernels at small shapes under the checker's capture context — no kernel
# body runs.  The pool is aliased in and out and only the slots the batch
# names are visited, so the uncovered-tile rule is waived for both.
# --------------------------------------------------------------------- #
from deepspeed_tpu.analysis.registry import pallas_kernel_case  # noqa: E402


def _dslint_gdn_inputs(rows: int, h: int = 8, dk: int = 128, dv: int = 128,
                       beta_scale: float = 1.0):
    import numpy as np

    rng = np.random.default_rng(2)
    f = lambda *shape: jnp.asarray(rng.standard_normal(shape), F32)
    return (f(5, h, dk, dv), f(rows, h, dk), f(rows, h, dk),
            f(rows, h, dv), -jnp.abs(f(rows, h)),
            beta_scale * jax.nn.sigmoid(f(rows, h)))


@pallas_kernel_case(
    "gdn_step", allow=("pallas-uncovered-tile",),
    note="gated delta rule decode update: one read and one write of each "
         "row's state slot; slots no row names keep their aliased content")
def _dslint_gdn_step():
    gdn_step(*_dslint_gdn_inputs(8), jnp.asarray([1, 0, 4, 3, 4, 4, 2, 4]),
             jnp.zeros((8,), bool), interpret=True)


@pallas_kernel_case(
    "gdn_chunk", allow=("pallas-uncovered-tile",),
    note="chunked gated delta rule over the tile segment, two phases a grid "
         "step (no scratch: phase A's results are values); the state is "
         "carried in the output block across a sequence's tiles")
def _dslint_gdn_chunk():
    gdn_chunk(*_dslint_gdn_inputs(512), jnp.asarray([2, 2, 0, 4]),
              jnp.asarray([True, False, False, False]), 128, interpret=True)


def _dslint_gdn_olmo_inputs(rows: int):
    """Olmo-Hybrid's shape class, the pool stored as the layout rule says:
    6 heads of 96 x 192 as three PAIRS ``[3, 96, 384]``."""
    pool, *rest = _dslint_gdn_inputs(rows, 6, 96, 192, 2.0)
    return (_pairs(pool), *rest)


@pallas_kernel_case(
    "gdn_step_h6_96x192", allow=("pallas-uncovered-tile",),
    note="the decode update at Olmo-Hybrid's shape class on the pool of "
         "head pairs: a group of 3 pairs (no multiple of 8) as whole "
         "trailing axes of 4-D row blocks, 96 keys x 2 x 192 values (three "
         "whole lane tiles), q and k columns by lane half, beta in (0, 2)")
def _dslint_gdn_step_olmo():
    gdn_step(*_dslint_gdn_olmo_inputs(8),
             jnp.asarray([1, 0, 4, 3, 4, 4, 2, 4]), jnp.zeros((8,), bool),
             interpret=True)


@pallas_kernel_case(
    "gdn_chunk_h6_96x192", allow=("pallas-uncovered-tile",),
    note="the chunked rule at Olmo-Hybrid's shape class on the pool of "
         "head pairs: a group of 3 pairs, q / k / scalars as [3, 2, tile, "
         ".] blocks (a pair's heads one under the other), states of 96 x "
         "384, beta in (0, 2)")
def _dslint_gdn_chunk_olmo():
    gdn_chunk(*_dslint_gdn_olmo_inputs(256), jnp.asarray([2, 0]),
              jnp.asarray([True, False]), 128, interpret=True)
