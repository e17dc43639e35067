"""The chunked gated delta rule's Mosaic kernel (``ops/gated_delta_rule.py::
_gdn_chunk_kernel``) in interpret mode against its XLA composition
(``gdn_chunk_reference``), at the edges its two phases and its carry have:
the slot carried across a sequence's tiles, a slot change at a tile
boundary, ``reset``, pad tiles on the scratch slot, one and two chunks a
tile, a head block equal to and below the head count; and the live
triangular inverse alone against ``numpy.linalg.inv``.

Decays are the Qwen3-Next cell's (a token keeps 85-100% of the state), so a
carry that is dropped or read from the wrong slot moves the result by its
own scale.  Tolerances are ``test_ragged_qwen3_next.py``'s.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.ops import gated_delta_rule as gdr


def _inputs(rows, h, dk=16, dv=16, slots=5, seed=0):
    rng = np.random.default_rng(seed)
    unit = lambda y: y / np.sqrt((y * y).sum(-1, keepdims=True) + 1e-6)
    f = lambda a: jnp.asarray(a, jnp.float32)
    return (f(rng.standard_normal((slots + 1, h, dk, dv))),
            f(unit(rng.standard_normal((rows, h, dk))) * dk ** -0.5),
            f(unit(rng.standard_normal((rows, h, dk)) + 0.5)),
            f(rng.standard_normal((rows, h, dv))),
            f(-0.05 * np.abs(rng.standard_normal((rows, h)))),
            f(1 / (1 + np.exp(-rng.standard_normal((rows, h))))))


def _masked(g, beta, real):
    real = jnp.asarray(real)[:, None]
    return jnp.where(real, g, 0.0), jnp.where(real, beta, 0.0)


def _agree(got, want, real=None, slots=slice(None)):
    (got_o, got_pool), (want_o, want_pool) = got, want
    real = slice(None) if real is None else np.asarray(real)
    got_o, want_o = np.asarray(got_o)[real], np.asarray(want_o)[real]
    assert np.max(np.abs(got_o - want_o)) <= 1e-5 * np.max(np.abs(want_o))
    got_pool, want_pool = np.asarray(got_pool)[slots], \
        np.asarray(want_pool)[slots]
    assert np.max(np.abs(got_pool - want_pool)) \
        <= 1e-5 * np.max(np.abs(want_pool))


#: tile, tile_slot, tile_reset, rows that are pad (a suffix of a tile)
CARRY_CASES = {
    # (a) one sequence over four tiles: the state is carried in the output
    # block, read from the pool once and from position > 0 (no reset)
    "one_sequence_four_tiles": (64, [3, 3, 3, 3], [0, 0, 0, 0], {}),
    # (b) the slot changes at tile boundaries: A (continues), B (from 0), A's
    # neighbour C (continues), single tiles and pairs
    "slots_change_between_sequences": (
        64, [1, 1, 4, 4, 0, 2], [0, 0, 1, 0, 0, 1], {3: 7, 5: 20}),
    # (c) reset on a sequence's first tile only: the slot's old content is
    # dropped once, then carried
    "reset_on_the_first_tile_only": (64, [2, 2, 2], [1, 0, 0], {2: 11}),
    # two chunks a tile and a sequence that ends inside a tile's first chunk
    "two_chunks_a_tile": (128, [0, 0, 3], [1, 0, 0], {1: 70, 2: 100}),
}


@pytest.mark.parametrize("name", sorted(CARRY_CASES))
def test_kernel_carries_the_state_as_the_composition_does(name):
    tile, slot, reset, short = CARRY_CASES[name]
    rows = tile * len(slot)
    pool, q, k, v, g, beta = _inputs(rows, 4, seed=len(name))
    real = np.ones(rows, bool)
    for t, pad in short.items():
        real[(t + 1) * tile - pad:(t + 1) * tile] = False
    g, beta = _masked(g, beta, real)
    args = (pool, q, k, v, g, beta, jnp.asarray(slot, jnp.int32),
            jnp.asarray(reset, bool), tile)
    got = gdr.gdn_chunk(*args, interpret=True)
    _agree(got, gdr.gdn_chunk_reference(*args), real, slice(0, 5))
    # slots no tile names are bitwise as they were
    for s in set(range(6)) - set(slot):
        assert np.array_equal(np.asarray(got[1])[s], np.asarray(pool)[s])


def test_dropping_the_carry_would_be_seen():
    """The cases above can tell: the same sequence with every tile reading
    the pool's old slot (what a lost carry does) is far outside the limit."""
    tile, rows = 64, 256
    pool, q, k, v, g, beta = _inputs(rows, 4, seed=3)
    slot = jnp.asarray([3, 3, 3, 3], jnp.int32)
    want, _ = gdr.gdn_chunk_reference(pool, q, k, v, g, beta, slot,
                                      jnp.zeros(4, bool), tile)
    lost = jnp.concatenate([gdr.gdn_chunk_reference(
        pool, *(x[t * tile:(t + 1) * tile] for x in (q, k, v, g, beta)),
        slot[:1], jnp.zeros(1, bool), tile)[0] for t in range(4)])
    assert np.max(np.abs(np.asarray(lost - want))) \
        > 1e-2 * np.max(np.abs(np.asarray(want)))


@pytest.mark.parametrize("tile", [64, 128])
def test_a_tile_of_pad_rows_writes_the_scratch_slot_back_as_it_was(tile):
    """(d) ``g`` 0 and ``beta`` 0 leave a state EXACTLY as it was, whatever
    q, k and v hold there; the live tiles around it are not disturbed."""
    rows, scratch = tile * 4, 5
    pool, q, k, v, g, beta = _inputs(rows, 4, seed=5)
    real = np.ones(rows, bool)
    real[tile:2 * tile] = False
    real[3 * tile:] = False
    g, beta = _masked(g, beta, real)
    args = (pool, q, k, v, g, beta,
            jnp.asarray([1, scratch, 3, scratch], jnp.int32),
            jnp.asarray([1, 0, 0, 0], bool), tile)
    got = gdr.gdn_chunk(*args, interpret=True)
    _agree(got, gdr.gdn_chunk_reference(*args), real, slice(0, 5))
    assert np.array_equal(np.asarray(got[1])[scratch],
                          np.asarray(pool)[scratch])


@pytest.mark.parametrize("tile,h,hb", [(64, 4, 4), (64, 8, 4), (128, 4, 4),
                                       (128, 8, 4), (128, 8, 8), (128, 8, 2),
                                       (32, 4, 4), (16, 4, 2)])
def test_head_blocks_and_tiles(tile, h, hb):
    """(e) one and two chunks a tile (and tiles under a chunk), the head
    block equal to and below the head count, dk != dv."""
    rows = tile * 3
    pool, q, k, v, g, beta = _inputs(rows, h, dk=16, dv=32, seed=tile + h)
    real = np.arange(rows) < rows - tile // 2 - 3
    g, beta = _masked(g, beta, real)
    slot = jnp.asarray([4, 0, 0], jnp.int32)
    reset = jnp.asarray([0, 1, 0], bool)
    got = gdr._gdn_chunk_call(pool, q, k, v, g, beta, slot, reset, tile,
                              min(gdr.CHUNK, tile), hb, True)
    _agree(got, gdr.gdn_chunk_reference(pool, q, k, v, g, beta, slot, reset,
                                        tile), real, slice(0, 5))


def test_public_entry_takes_a_divisor_of_the_heads_when_the_block_does_not_divide():
    assert [gdr._head_block(*a) for a in ((32, 8), (32, 4), (30, 8), (30, 4),
                                          (6, 4), (7, 4))] == \
        [8, 4, 6, 3, 3, 1]
    tile, rows = 64, 128
    pool, q, k, v, g, beta = _inputs(rows, 6, seed=9)
    args = (pool, q, k, v, g, beta, jnp.asarray([2, 2], jnp.int32),
            jnp.asarray([0, 0], bool), tile)
    _agree(gdr.gdn_chunk(*args, interpret=True),
           gdr.gdn_chunk_reference(*args), slots=slice(0, 5))


# ------------------------------------------------------------------ #
# (f) the inverse alone
# ------------------------------------------------------------------ #
def _strictly_lower(c, rng, batch=()):
    """``a`` as a chunk forms it: ``beta_i (k_i . k_j) exp(G_i - G_j)``
    below the diagonal, keys unit with a common part, the cell's decays."""
    k = rng.standard_normal(batch + (c, 24)) + 0.5
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    beta = 1 / (1 + np.exp(-rng.standard_normal(batch + (c, 1))))
    gc = np.cumsum(-0.05 * np.abs(rng.standard_normal(batch + (c,))), -1)
    decay = np.exp(gc[..., :, None] - gc[..., None, :])
    return np.tril(beta * (k @ np.swapaxes(k, -1, -2)) * decay, -1)


@pytest.mark.parametrize("c", [4, 8, 16, 32, 64, 128])
@pytest.mark.parametrize("batch", [(), (3,)], ids=["one", "batched"])
def test_live_inverse_against_linalg_inv(c, batch):
    a = _strictly_lower(c, np.random.default_rng(c), batch)
    got = np.asarray(gdr._tri_inverse_live(jnp.asarray(a, jnp.float32)))
    want = np.linalg.inv(np.eye(c) + a)
    assert np.max(np.abs(got - want)) <= 1e-6
    # and what the composition's full-width doubling gives
    old = np.asarray(gdr._tri_inverse(jnp.asarray(a, jnp.float32)))
    assert np.max(np.abs(got - old)) <= 1e-6


def test_live_inverse_is_exact_where_a_neumann_series_cancels():
    """All keys alike: ``a`` is the strictly lower matrix of ones, whose
    powers reach 1e17 while the inverse is bidiagonal."""
    c = 64
    inv = np.asarray(gdr._tri_inverse_live(
        jnp.tril(jnp.ones((c, c), jnp.float32), -1)))
    assert np.max(np.abs(inv - (np.eye(c) - np.eye(c, k=-1)))) <= 1e-6


def test_live_inverse_multiplies_a_quarter_of_the_rows(monkeypatch):
    """The products of the inverse by the rows of their left operand (what
    the MXU is pushed, a 128-lane column tile of the output each): six
    levels of two 64-row products before, four levels of two 16-row
    products and one merge of two 32-row products now."""
    rows = {"live": 0, "full": 0}
    real_mm, real_matmul = gdr._mm, jnp.matmul

    def counting(which, real):
        def mm(a, b, *args, **kw):
            rows[which] += a.shape[-2]
            return real(a, b, *args, **kw)
        return mm

    a = jnp.asarray(_strictly_lower(64, np.random.default_rng(1)),
                    jnp.float32)
    monkeypatch.setattr(gdr, "_mm", counting("live", real_mm))
    monkeypatch.setattr(jnp, "matmul", counting("full", real_matmul))
    gdr._tri_inverse_live(a)
    assert rows == {"live": 8 * 16 + 2 * 32, "full": 0}
    gdr._tri_inverse(a)
    assert rows["full"] == 12 * 64


# ------------------------------------------------------------------ #
# (g) Olmo-Hybrid's shape class: heads that are no multiple of 4 or 8,
# dk != dv, neither a multiple of 128, write strengths in (0, 2)
# ------------------------------------------------------------------ #
OLMO_SHAPES = [(30, 96, 192), (6, 24, 48)]
#: ... and the widths the layout rule stores as head PAIRS (``[H / 2, dk,
#: 2 dv]``, two heads side by side on the lanes): the published one and a
#: small one of the same class
PAIR_SHAPES = [(30, 96, 192), (6, 24, 64)]
#: (h, dk, dv, the pool the kernel is handed)
LAYOUT_CASES = [pytest.param(*shape, layout, id=f"{name}-{layout}")
                for shape, name, layout in (
                    (OLMO_SHAPES[0], "published", "natural"),
                    (OLMO_SHAPES[1], "small", "natural"),
                    (PAIR_SHAPES[0], "published", "pairs"),
                    (PAIR_SHAPES[1], "small", "pairs"))]


def _laid(pool, layout):
    return gdr._pairs(pool) if layout == "pairs" else pool


def _natural(got, layout):
    """A kernel's ``(o, pool)`` with the pool as the mathematics has it."""
    return (got[0], gdr._unpairs(got[1])) if layout == "pairs" else got


def _strong(beta, rng, share=0.3):
    """Write strengths in (0, 2) with ``share`` of them above 1.9."""
    beta = 2.0 * np.asarray(beta)
    high = rng.random(beta.shape) < share
    return jnp.asarray(np.where(high, rng.uniform(1.9, 2.0, beta.shape),
                                beta), jnp.float32)


@pytest.mark.parametrize("h,dk,dv,want", [
    (30, 96, 192, (15, 96, 384)),       # Olmo-Hybrid: three whole tiles
    (6, 24, 64, (3, 24, 128)),          # the small pair class
    (32, 128, 128, (32, 128, 128)),     # Qwen3-Next: whole tiles already
    (6, 24, 48, (6, 24, 48)),           # two heads fill no tile either
    (15, 96, 192, (15, 96, 192)),       # an odd head count
    (30, 96, 320, (15, 96, 640)),       # 2.5 tiles: a pair is 5, whole
])
def test_the_layout_rule(h, dk, dv, want):
    """ONE function says what a state is stored as, from the widths alone;
    a paired pool is recognised by its shape against the rows', and a pool
    that is neither layout is refused by name."""
    assert gdr.state_leaf_shape(h, dk, dv) == want
    f = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32)
    q, v = f(4, h, dk), f(4, h, dv)
    assert gdr._paired(f(9, *want), q, v) == (want != (h, dk, dv))
    assert not gdr._paired(f(9, h, dk, dv), q, v)
    with pytest.raises(ValueError, match="neither"):
        gdr._paired(f(9, h, dk, dv + 128), q, v)
    pool = jnp.arange(2 * h * dk * dv, dtype=jnp.float32).reshape(
        2, h, dk, dv)
    if h % 2 == 0:
        pairs = gdr._pairs(pool)
        assert np.array_equal(gdr._unpairs(pairs), pool)
        # lanes below dv are the even head
        assert np.array_equal(pairs[:, 1, :, :dv], pool[:, 2])
        assert np.array_equal(pairs[:, 1, :, dv:], pool[:, 3])


@pytest.mark.parametrize("h,dk,dv,layout", LAYOUT_CASES)
def test_step_kernel_at_olmo_hybrids_shape_class(h, dk, dv, layout):
    """One token a row at write strengths up to 2: the kernel (on the
    PAIRED pool where the layout says so) against the composition on the
    natural pool, and every slot no row names (the scratch slot too, which
    the pad row writes ``g = 0``, ``beta = 0`` to) bit-equal before and
    after."""
    rows, slots = 5, 6
    pool, q, k, v, g, beta = _inputs(rows, h, dk, dv, slots=slots, seed=h)
    real = np.asarray([1, 1, 1, 1, 0], bool)
    beta = _strong(beta, np.random.default_rng(h))
    g, beta = _masked(g, beta, real)
    where = (jnp.asarray([4, 0, 2, 5, slots], jnp.int32),
             jnp.asarray([0, 1, 0, 0, 0], bool))
    got = _natural(gdr.gdn_step(_laid(pool, layout), q, k, v, g, beta,
                                *where, interpret=True), layout)
    _agree(got, gdr.gdn_step_reference(pool, q, k, v, g, beta, *where), real)
    before, after = np.asarray(pool), np.asarray(got[1])
    assert np.array_equal(before[[1, 3, slots]], after[[1, 3, slots]])
    assert not np.array_equal(before[4], after[4])
    # the composition takes the paired pool too, and answers in pairs
    if layout == "pairs":
        ref = gdr.gdn_step_reference(gdr._pairs(pool), q, k, v, g, beta,
                                     *where)
        assert ref[1].shape == gdr._pairs(pool).shape
        _agree(_natural(ref, layout), got, real)


@pytest.mark.parametrize("h,dk,dv,layout", LAYOUT_CASES)
def test_chunk_kernel_at_olmo_hybrids_shape_class(h, dk, dv, layout):
    """The tile segment at write strengths up to 2 over 64-row chunks: a
    sequence over two tiles, one from a reset, a pad tile on the scratch
    slot; untouched slots and the scratch slot bit-equal before and
    after.  The kernel on the PAIRED pool where the layout says so, the
    composition on the natural one."""
    tile, slots = 64, 5
    slot, reset = [3, 3, 1, slots], [0, 0, 1, 0]
    rows = tile * len(slot)
    pool, q, k, v, g, beta = _inputs(rows, h, dk, dv, slots=slots, seed=dk)
    real = np.ones(rows, bool)
    real[3 * tile - 9:] = False         # a short tile, then the pad tile
    beta = _strong(beta, np.random.default_rng(dk))
    g, beta = _masked(g, beta, real)
    args = (q, k, v, g, beta, jnp.asarray(slot, jnp.int32),
            jnp.asarray(reset, bool), tile)
    got = _natural(gdr.gdn_chunk(_laid(pool, layout), *args, interpret=True),
                   layout)
    _agree(got, gdr.gdn_chunk_reference(pool, *args), real, slice(0, slots))
    before, after = np.asarray(pool), np.asarray(got[1])
    assert np.array_equal(before[[0, 2, 4, slots]], after[[0, 2, 4, slots]])
    if layout == "pairs":
        ref = gdr.gdn_chunk_reference(gdr._pairs(pool), *args)
        assert ref[1].shape == gdr._pairs(pool).shape
        _agree(_natural(ref, layout), got, real, slice(0, slots))
    # and token by token, what the chunked form has to equal
    o, p = [], pool
    for t in range(3 * tile - 9):
        first = t % tile == 0 and bool(reset[t // tile])
        ot, p = gdr.gdn_step_reference(
            p, q[t:t + 1], k[t:t + 1], v[t:t + 1], g[t:t + 1],
            beta[t:t + 1], jnp.asarray([slot[t // tile]], jnp.int32),
            jnp.asarray([first]))
        o.append(ot)
    _agree((got[0][:3 * tile - 9], got[1]), (jnp.concatenate(o), p),
           slots=slice(0, slots))


@pytest.mark.parametrize("tile", [16, 32, 128])
def test_chunk_kernel_on_pairs_at_other_tiles(tile):
    """The paired form with tiles under a chunk (the two heads' systems one
    ``[2 tile, 2 tile]`` block-diagonal system) and two chunks a tile."""
    h, dk, dv = PAIR_SHAPES[1]
    slot, reset = [4, 0, 0], [0, 1, 0]
    rows = tile * len(slot)
    pool, q, k, v, g, beta = _inputs(rows, h, dk, dv, seed=tile)
    real = np.arange(rows) < rows - tile // 2 - 3
    g, beta = _masked(g, _strong(beta, np.random.default_rng(tile)), real)
    args = (q, k, v, g, beta, jnp.asarray(slot, jnp.int32),
            jnp.asarray(reset, bool), tile)
    got = _natural(gdr.gdn_chunk(gdr._pairs(pool), *args, interpret=True),
                   "pairs")
    _agree(got, gdr.gdn_chunk_reference(pool, *args), real, slice(0, 5))


@pytest.mark.parametrize("kernel", ["step", "chunk"])
def test_the_odd_head_reading_the_even_heads_key_would_be_seen(kernel,
                                                               monkeypatch):
    """The fault the paired form can have and the natural one cannot: a
    lane half that takes its pair's OTHER head's column.  Here every odd
    head is given its even neighbour's ``k``: what a kernel that ignores
    the lane half computes.  Far outside the limit of the cases above."""
    h, dk, dv = PAIR_SHAPES[1]
    tile = 64
    rows = 5 if kernel == "step" else tile
    pool, q, k, v, g, beta = _inputs(rows, h, dk, dv, seed=7)
    beta = _strong(beta, np.random.default_rng(7))
    where = (jnp.asarray([4, 0, 2, 5, 1], jnp.int32),
             jnp.zeros(5, bool)) if kernel == "step" else \
        (jnp.asarray([2], jnp.int32), jnp.zeros(1, bool), tile)
    entry, ref = (gdr.gdn_step, gdr.gdn_step_reference) \
        if kernel == "step" else (gdr.gdn_chunk, gdr.gdn_chunk_reference)
    want = ref(pool, q, k, v, g, beta, *where)
    wrong_k = jnp.repeat(k[:, 0::2], 2, axis=1)
    got = _natural(entry(gdr._pairs(pool), q, wrong_k, v, g, beta, *where,
                         interpret=True), "pairs")
    odd = np.max(np.abs(np.asarray(got[0] - want[0])[:, 1::2]))
    assert odd > 1e-2 * np.max(np.abs(np.asarray(want[0])))
    # the even heads, whose key is their own, are untouched by it
    _agree((got[0][:, 0::2], got[1][:, 0::2]),
           (want[0][:, 0::2], want[1][:, 0::2]))


@pytest.mark.parametrize("share", [0.0, 0.3, 1.0],
                         ids=["to_2", "a_third_above_1.9", "all_above_1.9"])
def test_live_inverse_at_write_strengths_up_to_two(share):
    """``beta`` in (0, 2): the unit-lower system's off-diagonal entries
    reach 2 in magnitude and the inverse's are larger.  Block doubling is
    exact in exact arithmetic; in float32 it holds a float64 solve to
    rounding over a 64-row chunk, at the worst share of strong writes."""
    rng = np.random.default_rng(56)
    c = 64
    k = rng.standard_normal((5, c, 96)) + 0.5
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    beta = np.asarray(_strong(
        1 / (1 + np.exp(-rng.standard_normal((5, c, 1)))), rng, share))
    gc = np.cumsum(-0.002 * np.abs(rng.standard_normal((5, c))), -1)
    decay = np.exp(gc[..., :, None] - gc[..., None, :])
    a = np.tril(beta * (k @ np.swapaxes(k, -1, -2)) * decay, -1)
    want = np.stack([np.linalg.solve(np.eye(c) + m, np.eye(c)) for m in a])
    got = np.asarray(gdr._tri_inverse_live(jnp.asarray(a, jnp.float32)))
    scale = np.max(np.abs(want))
    assert np.max(np.abs(got - want)) <= 2e-6 * max(scale, 1.0), scale


@pytest.mark.parametrize("pool,hb", [((129, 15, 96, 384), (5, 3)),
                                     ((129, 30, 96, 192), (6, 3))],
                         ids=["pairs", "natural"])
def test_olmo_hybrids_calls_lower_for_the_tpu_under_the_kernels_names(pool,
                                                                      hb):
    """128 one-token rows and 1,024 tile rows at 30 heads of 96 x 192 over
    129 slots, on the pool of head pairs the layout rule gives (and on the
    natural one): one ``pallas_call`` each, under the names the per-layer
    readers match, the pool aliased in and out."""
    assert (gdr._head_block(pool[1], 8), gdr._head_block(pool[1], 4)) == hb
    s = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32)
    ints = lambda n, dt=jnp.int32: jax.ShapeDtypeStruct((n,), dt)
    step = jax.jit(lambda *a: gdr._gdn_step_call(
        *a, hb=hb[0], interpret=False)).trace(
        s(*pool), s(128, 30, 96), s(128, 30, 96),
        s(128, 30, 192), s(128, 30), s(128, 30), ints(128),
        ints(128, jnp.bool_)).lower(lowering_platforms=("tpu",)).as_text()
    chunk = jax.jit(lambda *a: gdr._gdn_chunk_call(
        *a, tile=128, chunk=64, hb=hb[1], interpret=False)).trace(
        s(*pool), s(1024, 30, 96), s(1024, 30, 96),
        s(1024, 30, 192), s(1024, 30), s(1024, 30), ints(8),
        ints(8, jnp.bool_)).lower(lowering_platforms=("tpu",)).as_text()
    for text, name in ((step, "_gdn_step_kernel"),
                       (chunk, "_gdn_chunk_kernel")):
        assert text.count("tpu_custom_call") == 1
        assert f'kernel_name = "{name}"' in text
        assert "output_operand_alias<output_tuple_indices = [1], " \
            "operand_index = 7" in text


def test_the_cells_call_lowers_for_the_tpu_under_the_kernels_name():
    """1,024 rows, 32 heads, tile 128, 33 slots: one ``pallas_call`` named
    ``_gdn_chunk_kernel`` (the per-layer readers match that name), the pool
    aliased in and out."""
    s = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32)
    text = jax.jit(lambda *a: gdr._gdn_chunk_call(
        *a, tile=128, chunk=64, hb=4, interpret=False)).trace(
        s(33, 32, 128, 128), s(1024, 32, 128), s(1024, 32, 128),
        s(1024, 32, 128), s(1024, 32), s(1024, 32),
        jax.ShapeDtypeStruct((8,), jnp.int32),
        jax.ShapeDtypeStruct((8,), jnp.bool_)).lower(
        lowering_platforms=("tpu",)).as_text()
    assert text.count("tpu_custom_call") == 1
    assert 'kernel_name = "_gdn_chunk_kernel"' in text
    assert "output_operand_alias<output_tuple_indices = [1], operand_index = 7" \
        in text
