"""Grouped expert GEMM kernels (ops/grouped_gemm.py) vs the XLA
reference composition — the reference-kernel test pattern (SURVEY §4:
Pallas kernel vs jnp reference, interpret mode on CPU).

Covers the dynamic-boundary cases that distinguish a grouped GEMM from a
batched one: group boundaries inside an m-tile (shared boundary tiles),
empty groups, groups spanning multiple tiles, rows past the last group,
and the custom-VJP backward kernels (dlhs + tgmm drhs).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.ops import grouped_gemm
from deepspeed_tpu.ops.grouped_gemm import (
    gmm,
    gmm_reference,
    grouped_moe_ffn,
    make_block_metadata,
    make_group_metadata,
)
from gmm_grid_pipeline import grid_pipeline_gmm

TM = TN = 128


def _case(m, k, n, e, sizes, seed=0, dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    lhs = jnp.asarray(rng.standard_normal((m, k)) * 0.1, dtype)
    rhs = jnp.asarray(rng.standard_normal((e, k, n)) * 0.1, dtype)
    gs = jnp.asarray(sizes, jnp.int32)
    assert int(gs.sum()) <= m and gs.shape[0] == e
    return lhs, rhs, gs


def test_metadata_covers_all_groups():
    gs = jnp.asarray([100, 0, 156, 200, 56], jnp.int32)  # sums to 512
    gids, mtids, rs, re_, nw = make_group_metadata(gs, 512, 128)
    gids, mtids, rs, re_ = map(np.asarray, (gids, mtids, rs, re_))
    nw = int(nw)
    # every row of every non-empty group is covered by exactly one unit
    covered = np.zeros(512, bool)
    ends = np.cumsum(np.asarray(gs))
    starts = ends - np.asarray(gs)
    for w in range(nw):
        lo = max(mtids[w] * 128, rs[w])
        hi = min((mtids[w] + 1) * 128, re_[w])
        assert not covered[lo:hi].any(), "row covered twice"
        covered[lo:hi] = True
        assert starts[gids[w]] == rs[w] and ends[gids[w]] == re_[w]
    assert covered.all()
    # invalid units duplicate the last valid one with empty ranges
    for w in range(nw, len(gids)):
        assert gids[w] == gids[nw - 1] and mtids[w] == mtids[nw - 1]
        assert rs[w] == re_[w] == 0


@pytest.mark.parametrize("sizes", [
    [128, 128, 128, 128],          # tile-aligned
    [100, 156, 200, 56],           # boundaries inside tiles
    [0, 512, 0, 0],                # empty groups, one giant group
    [511, 1, 0, 0],                # 1-row group sharing a tile
])
def test_gmm_forward_parity(sizes):
    lhs, rhs, gs = _case(512, 64, 256, 4, sizes)
    got = gmm(lhs, rhs, gs, TM, TN, True)
    want = gmm_reference(lhs, rhs, gs)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-5, rtol=1e-5)


def test_gmm_rows_past_last_group_are_zero():
    lhs, rhs, gs = _case(512, 64, 128, 3, [100, 100, 56])  # 256 < 512
    got = np.asarray(gmm(lhs, rhs, gs, TM, TN, True))
    assert np.all(got[256:] == 0)
    want = np.asarray(gmm_reference(lhs, rhs, gs))
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=1e-5)


def test_gmm_grad_parity():
    lhs, rhs, gs = _case(256, 64, 128, 4, [60, 0, 130, 66], seed=3)

    def f_kernel(lhs, rhs):
        return jnp.sum(gmm(lhs, rhs, gs, TM, TN, True) ** 2)

    def f_ref(lhs, rhs):
        return jnp.sum(gmm_reference(lhs, rhs, gs) ** 2)

    gk = jax.grad(f_kernel, argnums=(0, 1))(lhs, rhs)
    gr = jax.grad(f_ref, argnums=(0, 1))(lhs, rhs)
    np.testing.assert_allclose(np.asarray(gk[0]), np.asarray(gr[0]),
                               atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(gk[1]), np.asarray(gr[1]),
                               atol=1e-4, rtol=1e-4)
    # empty expert: exactly zero gradient
    assert np.all(np.asarray(gk[1])[1] == 0)


def test_gmm_grad_rows_past_last_group_are_zero():
    """Backward contract for groups not filling M: dlhs rows past the
    last group are exactly zero (never-visited tiles must not leak
    uninitialised memory into gradients)."""
    lhs, rhs, gs = _case(512, 64, 128, 3, [100, 100, 56], seed=5)

    def f(lhs, rhs):
        return jnp.sum(gmm(lhs, rhs, gs, TM, TN, True) ** 2)

    dlhs, drhs = jax.grad(f, argnums=(0, 1))(lhs, rhs)
    assert np.all(np.asarray(dlhs)[256:] == 0)
    gr = jax.grad(lambda a, b: jnp.sum(gmm_reference(a, b, gs) ** 2),
                  argnums=(0, 1))(lhs, rhs)
    np.testing.assert_allclose(np.asarray(dlhs), np.asarray(gr[0]),
                               atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(drhs), np.asarray(gr[1]),
                               atol=1e-4, rtol=1e-4)


# ------------------------------------------------------------------ #
# Group sizes a SHARE of a wider router's experts produces (PR 32): the
# groups sum to a fraction of m, so most of the static work-unit list is
# units with an empty row range, which the kernels skip.
# ------------------------------------------------------------------ #
def _share_sizes(name):
    """(m, group sizes) by case name."""
    rng = np.random.default_rng(32)
    e = 128
    uniform = np.full(e, 1.0 / e)
    if name == "quarter":        # the Qwen3-Next decode tick: 384 rows,
        return 384, rng.multinomial(81, uniform)   # 128 groups, ~80 live
    if name == "all_empty":      # a tick that routes nothing here
        return 384, np.zeros(e, np.int64)
    if name == "one_live":
        sizes = np.zeros(e, np.int64)
        sizes[77] = 5
        return 384, sizes
    if name == "tile_edge":      # the live rows end exactly on a tile edge
        return 384, rng.multinomial(256, uniform)
    assert name == "all_full"    # every boundary inside a tile: no unit
    return 512, np.asarray([100, 130, 150, 132])   # of the list is padding


SHARE_CASES = ["quarter", "all_empty", "one_live", "tile_edge", "all_full"]


def _parent_gmm_kernel(group_ids, m_tile_ids, row_start, row_end, lhs_ref,
                       rhs_ref, out_ref, *, tile_m: int):
    """``_gmm_kernel`` as it was before PR 32, kept as the oracle: every
    unit multiplies, a unit with an empty row range stores back what was
    there (and, as until PR 53, the weights come on the grid pipeline)."""
    w = pl.program_id(1)
    mt = m_tile_ids[w]
    rows = mt * tile_m + jax.lax.broadcasted_iota(
        jnp.int32, (tile_m, 1), 0)
    keep = (rows >= row_start[w]) & (rows < row_end[w])

    @pl.when(jnp.logical_or(w == 0, m_tile_ids[w - 1] != mt))
    def _():
        out_ref[:] = jnp.zeros_like(out_ref)

    partial = jax.lax.dot_general(
        lhs_ref[:], rhs_ref[0], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    out_ref[:] = jnp.where(keep, partial.astype(out_ref.dtype), out_ref[:])


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("name", SHARE_CASES)
def test_gmm_on_a_share_is_the_parents_kernel_bit_for_bit(name, dtype):
    m, sizes = _share_sizes(name)
    lhs, rhs, gs = _case(m, 64, 256, len(sizes), sizes, seed=32,
                         dtype=dtype)
    got = np.asarray(gmm(lhs, rhs, gs, TM, TN, True).astype(jnp.float32))
    want = np.asarray(gmm_reference(lhs, rhs, gs).astype(jnp.float32))
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(got, want, atol=tol, rtol=tol)
    assert np.all(got[int(sizes.sum()):] == 0)
    parent = grid_pipeline_gmm(lhs, rhs, gs, TM, TN, True,
                               kernel=_parent_gmm_kernel)
    np.testing.assert_array_equal(
        got, np.asarray(parent.astype(jnp.float32)))


# ------------------------------------------------------------------ #
# The weight ring (PR 53): the expert weights stay in HBM and the kernel
# fills ``slots`` VMEM slots itself, the block ``slots - 1`` places ahead
# started at a block's first unit, where the grid pipeline fetched one
# step ahead.  Same dot, same mask, same stores: the parent's bits.
# ------------------------------------------------------------------ #
#: name -> (m, n, group sizes) at 128 x 128 tiles, K = 64
RING_CASES = {
    # one expert over four row tiles, its neighbours inside its edge tiles
    "three_tiles_an_expert": (640, 256, [30, 420, 100, 90]),
    # empty experts between the live ones, at both ends too
    "empties_between": (512, 256, [0, 130, 0, 0, 200, 0, 182, 0]),
    # a share: 177 of 512 rows, the list's last five units hold nothing
    "dead_units_at_the_end": (512, 256, [100, 0, 70, 7, 0, 0]),
    # three n-tiles: the ring runs ahead across two n-tile boundaries
    "three_n_tiles": (384, 384, [140, 0, 128, 116]),
    # one block an n-tile: every block ahead is the next n-tile's
    "one_live_expert": (384, 384, [0, 0, 300, 0]),
    "one_live_expert_one_n_tile": (256, 128, [0, 5, 0]),
    # as many blocks as slots, and one fewer
    "two_blocks": (256, 256, [128, 128]),
    "nothing_routed_here": (256, 256, [0, 0, 0]),
}


@pytest.mark.parametrize("slots", [2, 3])
@pytest.mark.parametrize("name", RING_CASES)
def test_ring_is_the_grid_pipelines_kernel_bit_for_bit(name, slots):
    m, n, sizes = RING_CASES[name]
    lhs, rhs, gs = _case(m, 64, n, len(sizes), sizes, seed=53,
                         dtype=jnp.bfloat16)
    got = grouped_gemm._gmm_fwd_kernel_call(lhs, rhs, gs, TM, TN, True,
                                            slots).astype(jnp.float32)
    want = gmm_reference(lhs, rhs, gs).astype(jnp.float32)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-2, rtol=2e-2)
    assert np.all(np.asarray(got)[sum(sizes):] == 0)
    parent = grid_pipeline_gmm(lhs, rhs, gs, TM, TN, True)
    np.testing.assert_array_equal(
        np.asarray(got), np.asarray(parent.astype(jnp.float32)))


@pytest.mark.parametrize("slots", [2, 3])
@pytest.mark.parametrize("name", ["three_tiles_an_expert",
                                  "dead_units_at_the_end", "three_n_tiles",
                                  "one_live_expert"])
def test_ring_waits_for_every_block_it_reads(name, slots):
    """The TPU interpreter with copies that land when they are WAITED for,
    in buffers that start as NaN, with its race detector on: a block read
    before its wait, a slot started while its last reader could still run
    or a wait on a slot nothing was started into would show as NaN, as a
    race or as a hang.  (``interpret=True`` copies at the start.)"""
    from jax._src.pallas.mosaic.interpret import interpret_pallas_call

    m, n, sizes = RING_CASES[name]
    lhs, rhs, gs = _case(m, 64, n, len(sizes), sizes, seed=54)
    params = pltpu.InterpretParams(dma_execution_mode="on_wait",
                                   uninitialized_memory="nan",
                                   detect_races=True)
    got = grouped_gemm._gmm_fwd_kernel_call(lhs, rhs, gs, TM, TN, params,
                                            slots)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(gmm_reference(lhs, rhs, gs)),
                               atol=1e-5, rtol=1e-5)
    assert not interpret_pallas_call.races.races_found


@pytest.mark.parametrize("name", RING_CASES)
def test_block_metadata_names_the_walk(name):
    """``block_of`` / ``next_live`` / ``num_blocks`` against the walk
    itself: the live groups in rising order, the one after the last being
    the first again (the kernel reads ``next_live[g] <= g`` as "the next
    n-tile")."""
    _, _, sizes = RING_CASES[name]
    block_of, next_live, num_blocks = map(
        np.asarray, make_block_metadata(jnp.asarray(sizes, jnp.int32)))
    walk = [g for g, s in enumerate(sizes) if s]
    assert num_blocks.shape == (1,) and int(num_blocks[0]) == len(walk)
    for place, g in enumerate(walk):
        assert block_of[g] == place
        assert next_live[g] == walk[(place + 1) % len(walk)]
        assert (next_live[g] <= g) == (place == len(walk) - 1)
    assert np.all((next_live >= 0) & (next_live < len(sizes)))


@pytest.mark.parametrize("name", ["quarter", "one_live"])
def test_gmm_grads_on_a_share(name):
    """dlhs / drhs where most units are skipped: drhs flushes its last
    group at the LAST unit of the list, which is a skipped one here."""
    m, sizes = _share_sizes(name)
    lhs, rhs, gs = _case(m, 64, 128, len(sizes), sizes, seed=33)

    def loss(fn):
        return lambda a, b: jnp.sum(fn(a, b) ** 2)

    gk = jax.grad(loss(lambda a, b: gmm(a, b, gs, TM, TN, True)),
                  argnums=(0, 1))(lhs, rhs)
    gr = jax.grad(loss(lambda a, b: gmm_reference(a, b, gs)),
                  argnums=(0, 1))(lhs, rhs)
    for got, want in zip(gk, gr):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-4, rtol=1e-4)
    assert np.all(np.asarray(gk[0])[int(sizes.sum()):] == 0)
    assert np.all(np.asarray(gk[1])[sizes == 0] == 0)
    last = int(np.flatnonzero(sizes)[-1])
    assert np.any(np.asarray(gk[1])[last] != 0)


@pytest.mark.parametrize("name", SHARE_CASES)
def test_num_work_counts_the_units_that_hold_rows(name):
    m, sizes = _share_sizes(name)
    gids, mtids, rs, re_, nw = make_group_metadata(
        jnp.asarray(sizes, jnp.int32), m, TM)
    rs, re_ = np.asarray(rs), np.asarray(re_)
    assert rs.shape[0] == m // TM + len(sizes) - 1
    live = re_ > rs
    assert int(nw) == live.sum()
    assert live[:int(nw)].all()          # and they come first
    # a group of n rows starting at s touches these tiles, one unit each
    ends = np.cumsum(sizes)
    starts = ends - sizes
    assert int(nw) == sum((e - 1) // TM - s // TM + 1
                          for s, e in zip(starts, ends) if e > s)
    if name == "all_full":
        assert live.all()
    if name == "all_empty":
        assert not live.any()


def test_gmm_nondivisible_falls_back():
    lhs, rhs, gs = _case(100, 32, 48, 2, [60, 40])
    got = gmm(lhs, rhs, gs, TM, TN, True)   # 100 % 128 != 0 -> reference
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(gmm_reference(lhs, rhs, gs)),
                               atol=1e-6)


def test_grouped_moe_ffn_matches_dense_dropless():
    """grouped_moe_ffn == the dense all-experts dropless composition
    (modules/moe.py::dropless_moe's math) for identical routing."""
    rng = np.random.default_rng(7)
    t, h, f, e, k = 64, 64, 128, 4, 2
    x = jnp.asarray(rng.standard_normal((t, h)) * 0.1, jnp.float32)
    w_gate = jnp.asarray(rng.standard_normal((e, h, f)) * 0.1, jnp.float32)
    w_up = jnp.asarray(rng.standard_normal((e, h, f)) * 0.1, jnp.float32)
    w_down = jnp.asarray(rng.standard_normal((e, f, h)) * 0.1, jnp.float32)
    logits = jnp.asarray(rng.standard_normal((t, e)), jnp.float32)
    probs = jax.nn.softmax(logits, -1)
    topv, topi = jax.lax.top_k(probs, k)
    topw = topv / jnp.sum(topv, -1, keepdims=True)

    got = grouped_moe_ffn(x, topi, topw, w_gate, w_up, w_down,
                          interpret=True)

    comb = jnp.sum(jax.nn.one_hot(topi, e) * topw[..., None], axis=1)
    hmid = jax.nn.silu(jnp.einsum("th,ehf->etf", x, w_gate)) * \
        jnp.einsum("th,ehf->etf", x, w_up)
    dense = jnp.einsum("etf,efh,te->th", hmid, w_down, comb)
    np.testing.assert_allclose(np.asarray(got), np.asarray(dense),
                               atol=1e-4, rtol=1e-4)


def test_dropless_moe_layer_trains():
    """MOELayer(dropless=True): no capacity, exact top-k, grouped-GEMM
    experts — forward + grad must be finite and the param tree must be
    IDENTICAL to the capacity path's (checkpoints interop)."""
    import flax

    from deepspeed_tpu.moe.sharded_moe import MOELayer

    x = jnp.asarray(np.random.default_rng(9).standard_normal((2, 16, 32)),
                    jnp.float32)
    drop = MOELayer(num_experts=4, hidden=32, intermediate=64, k=2,
                    dtype=jnp.float32, dropless=True)
    cap = MOELayer(num_experts=4, hidden=32, intermediate=64, k=2,
                   dtype=jnp.float32)
    p1 = drop.init(jax.random.key(0), x)["params"]
    p2 = cap.init(jax.random.key(0), x)["params"]
    assert (jax.tree_util.tree_structure(p1)
            == jax.tree_util.tree_structure(p2))

    def loss(p):
        out, l_aux = drop.apply({"params": p}, x)
        return jnp.sum(out ** 2) + 0.01 * l_aux

    val, g = jax.value_and_grad(loss)(p1)
    assert np.isfinite(float(val))
    leaves = jax.tree_util.tree_leaves(g)
    assert all(np.all(np.isfinite(np.asarray(l))) for l in leaves)
    # router gradient flows (topw depends on wg)
    assert float(jnp.abs(g["gate"]["wg"]["kernel"]).sum()) > 0


def test_dropless_moe_matches_dense_math():
    """dropless MOELayer output == the dense dropless composition (every
    expert over every token, masked) with the same params."""
    from deepspeed_tpu.moe.sharded_moe import MOELayer

    rng = np.random.default_rng(11)
    x = jnp.asarray(rng.standard_normal((1, 8, 32)), jnp.float32)
    layer = MOELayer(num_experts=4, hidden=32, intermediate=64, k=2,
                     dtype=jnp.float32, dropless=True)
    p = layer.init(jax.random.key(1), x)["params"]
    out, _ = layer.apply({"params": p}, x)

    tokens = np.asarray(x).reshape(-1, 32)
    wg = np.asarray(p["gate"]["wg"]["kernel"])
    probs = jax.nn.softmax(jnp.asarray(tokens @ wg), -1)
    topv, topi = jax.lax.top_k(probs, 2)
    topw = topv / jnp.sum(topv, -1, keepdims=True)
    comb = jnp.sum(jax.nn.one_hot(topi, 4) * topw[..., None], axis=1)
    wgt = jnp.asarray(p["experts"]["w_gate"])
    wup = jnp.asarray(p["experts"]["w_up"])
    wdn = jnp.asarray(p["experts"]["w_down"])
    hmid = jax.nn.silu(jnp.einsum("th,ehf->etf", tokens, wgt)) * \
        jnp.einsum("th,ehf->etf", tokens, wup)
    dense = jnp.einsum("etf,efh,te->th", hmid, wdn, comb)
    np.testing.assert_allclose(np.asarray(out).reshape(-1, 32),
                               np.asarray(dense), atol=1e-4, rtol=1e-4)


def test_grouped_moe_ffn_differentiable():
    rng = np.random.default_rng(8)
    t, h, f, e, k = 32, 32, 64, 4, 2
    x = jnp.asarray(rng.standard_normal((t, h)) * 0.1, jnp.float32)
    ws = [jnp.asarray(rng.standard_normal(s) * 0.1, jnp.float32)
          for s in ((e, h, f), (e, h, f), (e, f, h))]
    topi = jnp.asarray(rng.integers(0, e, size=(t, k)), jnp.int32)
    topw = jnp.full((t, k), 0.5, jnp.float32)

    def loss(x, wg, wu, wd):
        return jnp.sum(grouped_moe_ffn(x, topi, topw, wg, wu, wd,
                                       interpret=True) ** 2)

    g = jax.grad(loss, argnums=(0, 1, 2, 3))(x, *ws)
    for gi in g:
        assert np.all(np.isfinite(np.asarray(gi)))
    assert float(jnp.abs(g[0]).sum()) > 0


# ------------------------------------------------------------------ #
# The combine (PR 60): the k choices gathered choice-major and summed as k
# written-out float32 terms, against the dense composition of every held
# expert over every token.
# ------------------------------------------------------------------ #
def _combine_case(t, k, share, dtype, seed):
    """Inputs of one combine case: 16 held experts; behind ``share`` they
    are ids [8, 24) of a router 32 wide, and tokens 0-2 choose outside
    the share alone."""
    rng = np.random.default_rng(seed)
    h, f, e = 64, 128, 16
    start, wide = (8, 32) if share else (None, e)
    x = jnp.asarray(rng.standard_normal((t, h)) * 0.5, dtype)
    ws = [jnp.asarray(rng.standard_normal(s) * 0.1, dtype)
          for s in ((e, h, f), (e, h, f), (e, f, h))]
    logits = rng.standard_normal((t, wide))
    if share:
        logits[:3, 8:24] -= 100.0
    topw, topi = jax.lax.top_k(jax.nn.softmax(jnp.asarray(
        logits, jnp.float32), -1), k)
    return x, topi.astype(jnp.int32), topw, ws, start


def _dense_moe(x, topi, topw, wg, wu, wd, start):
    """Every held expert over every token in float32, each token's k
    weights on its experts' columns; a choice outside the share has no
    column (``one_hot`` of an id out of range is a zero row)."""
    e = wg.shape[0]
    ids = topi - (start or 0)
    comb = jnp.sum(jax.nn.one_hot(ids, e) * topw[..., None], axis=1)
    x, wg, wu, wd = (a.astype(jnp.float32) for a in (x, wg, wu, wd))
    hmid = jax.nn.silu(jnp.einsum("th,ehf->etf", x, wg)) * \
        jnp.einsum("th,ehf->etf", x, wu)
    return jnp.einsum("etf,efh,te->th", hmid, wd, comb)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("share", [False, True], ids=["whole", "share"])
@pytest.mark.parametrize("t", [32, 40, 132])
@pytest.mark.parametrize("k", [4, 6, 8, 10, 12])
def test_combine_sums_k_slabs(k, t, share, dtype, monkeypatch):
    """``grouped_moe_ffn`` and its gradients against the dense composition
    at the cells' top-k (4 LFM2 / Trinity, 6 Moonlight, 8 OLMoE / GLM-5, 10
    Qwen3-Next / Granite, 12 LongCat), at token counts of whole bf16 row
    tiles (32), of whole float32 tiles only (40) and of neither (132 = 1056
    / 8), on all of a router's experts and on a share of them.  The down
    projection's rows past the last group, which the kernel leaves zero and
    a held-out choice's clamped gather may land on, are set to 1e30 here:
    a held-out choice has to weigh exactly nothing, so the result stays
    the reference's and a token with no expert in the share reads 0."""
    x, topi, topw, ws, start = _combine_case(t, k, share, dtype,
                                             seed=100 * k + t)
    f = ws[0].shape[2]
    real_gmm = grouped_gemm.gmm

    def poisoned_gmm(lhs, rhs, group_sizes, *a):
        out = real_gmm(lhs, rhs, group_sizes, *a)
        if rhs.shape[1] != f:                 # gate / up: left as they are
            return out
        past = jnp.arange(out.shape[0])[:, None] >= jnp.sum(group_sizes)
        return jnp.where(past, jnp.asarray(1e30, out.dtype), out)

    monkeypatch.setattr(grouped_gemm, "gmm", poisoned_gmm)

    def ours(x, topw, wg, wu, wd):
        return grouped_moe_ffn(x, topi, topw, wg, wu, wd, interpret=True,
                               expert_start=start).astype(jnp.float32)

    def dense(x, topw, wg, wu, wd):
        return _dense_moe(x, topi, topw, wg, wu, wd, start)

    # float32: the file's tolerance; bf16: the three GEMMs' outputs and
    # the SwiGLU product are each rounded to 8 bits on the way
    tol = dict(atol=1e-4, rtol=1e-4) if dtype == jnp.float32 \
        else dict(atol=3e-2, rtol=3e-2)
    got, want = ours(x, topw, *ws), dense(x, topw, *ws)
    assert got.shape == (t, x.shape[1])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), **tol)
    if share:
        assert np.all(np.asarray(got[:3]) == 0.0)

    probe = jnp.asarray(np.random.default_rng(k + t).standard_normal(
        got.shape), jnp.float32)
    g_ours = jax.grad(lambda *a: jnp.sum(ours(*a) * probe),
                      argnums=(0, 1, 2, 3, 4))(x, topw, *ws)
    g_dense = jax.grad(lambda *a: jnp.sum(dense(*a) * probe),
                       argnums=(0, 1, 2, 3, 4))(x, topw, *ws)
    for name, a, b in zip(("x", "topw", "w_gate", "w_up", "w_down"),
                          g_ours, g_dense):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        scale = max(float(np.abs(b).max()), 1.0)
        np.testing.assert_allclose(a / scale, b / scale, err_msg=name, **tol)


# ------------------------------------------------------------------ #
# The tile rules (PR 36): the forward's tiles from the rows an expert
# holds and the forward's own working set, the backward's from a working
# set with its accumulator.
# ------------------------------------------------------------------ #
#: name -> (m, hidden, expert width, experts held): the four MoE serving
#: cells' decode tick and full mixed tick (rows x top-k, padded to 128),
#: and two training shapes (8 experts, thousands of rows each)
RULE_SHAPES = {
    "lfm2_decode": (512, 2048, 1536, 64),
    "lfm2_T1152": (4608, 2048, 1536, 64),
    "olmoe_decode": (256, 2048, 1024, 64),
    "olmoe_T1056": (8448, 2048, 1024, 64),
    "qwen3next_decode": (384, 2048, 512, 128),
    "qwen3next_T1056": (10624, 2048, 512, 128),
    "moonlight_decode": (384, 2048, 1408, 16),
    "moonlight_T1088": (6528, 2048, 1408, 16),
    "train_8x4096": (32768, 2048, 1024, 8),
    "train_8x2048": (16384, 2048, 5632, 8),
}
#: the picks ISSUE 36 expects, (gate / up, down), where it names them;
#: Moonlight's gate / up takes the whole N = 11 x 128 under a raised limit
EXPECTED_TILES = {
    "lfm2_decode": ((128, 768), (128, 1024)),
    "lfm2_T1152": ((128, 768), (128, 1024)),
    "olmoe_decode": ((128, 1024), (128, 2048)),
    "olmoe_T1056": ((128, 1024), (128, 2048)),
    "qwen3next_decode": ((128, 512), (128, 2048)),
    "qwen3next_T1056": ((128, 512), (128, 2048)),
    "moonlight_decode": ((128, 1408), (128, 1024)),
    "moonlight_T1088": ((128, 1408), (128, 1024)),
}
#: the ring's slots at those picks (gate / up, down): OLMoE's 4 MiB blocks
#: leave no room for a third under 12 MiB, and Moonlight's whole-N call,
#: over the default budget at two, stays at two
EXPECTED_SLOTS = {
    "lfm2_decode": (3, 3), "lfm2_T1152": (3, 3),
    "olmoe_decode": (2, 2), "olmoe_T1056": (2, 2),
    "qwen3next_decode": (3, 3), "qwen3next_T1056": (3, 3),
    "moonlight_decode": (2, 3), "moonlight_T1088": (2, 3),
}


@pytest.mark.parametrize("call", ["gate_up", "down"])
@pytest.mark.parametrize("name", RULE_SHAPES)
def test_tile_rules_arithmetic(name, call):
    gg = grouped_gemm
    m, h, f, e = RULE_SHAPES[name]
    k, n = (h, f) if call == "gate_up" else (f, h)
    tm, tn = gg._pick_tiles(m, k, n, e)
    assert m % tm == 0 and n % tn == 0 and tn % 128 == 0
    # the forward holds double-buffered bf16 lhs and out blocks and the
    # weight ring; at two slots (what the tiles are picked under) that is
    # the grid pipeline's three double-buffered blocks; no accumulator
    need = 2 * 2 * (tm * k + k * tn + tm * tn)
    assert gg._forward_vmem(tm, k, tn) == need
    assert gg._forward_vmem(tm, k, tn, 2, 3) == need + 2 * k * tn
    # a third slot only inside the default budget
    slots = gg._ring_slots(tm, k, tn)
    assert slots == (3 if need + 2 * k * tn <= gg._VMEM_BUDGET else 2)
    if name in EXPECTED_SLOTS:
        assert slots == EXPECTED_SLOTS[name][call == "down"]
    if need > gg._VMEM_BUDGET:
        # only the whole of an N that has no column tile between 128 and
        # itself, at 128 rows, and the call then brings its own limit
        assert (tm, tn) == (128, n) and need <= gg._VMEM_RAISED_BUDGET
        assert not [d for d in range(256, n, 128) if n % d == 0
                    and gg._forward_vmem(128, k, d) <= gg._VMEM_BUDGET]
    if name in EXPECTED_TILES:
        assert (tm, tn) == EXPECTED_TILES[name][call == "down"]
    # the backward kernels get their own, each with its accumulator
    bm, bn = gg._pick_backward_tiles(m, k, n)
    assert m % bm == 0 and n % bn == 0
    blocks = 4 * (bm * k + k * bn + bm * bn)
    assert blocks + 4 * bm * k <= gg._VMEM_BUDGET          # dlhs
    assert blocks + 4 * k * bn <= gg._VMEM_BUDGET          # drhs
    rows = m / e
    if name.startswith("train"):
        # thousands of rows a group: the tall tiles the parent's rule
        # gave (the largest dividing m that VMEM lets have a column tile)
        assert tm >= 256 and 8 * tm <= rows and tn >= gg._MXU_BOUND_ROWS
    elif rows + 128 <= gg._MXU_BOUND_ROWS:
        # small groups: the padded passes stay under the weight stream
        assert rows + tm <= gg._MXU_BOUND_ROWS
    else:
        assert tm == 128         # nothing hides them: the least padding


def test_pick_tiles_without_the_groups_takes_the_smallest_row_tile():
    """Three positional arguments keep working (chip_smoke's and the
    benchmark's one-off callers): with the groups unknown, 128 rows."""
    assert grouped_gemm._pick_tiles(512, 2048, 1536) == (128, 768)
    assert grouped_gemm._pick_tiles(32768, 2048, 1024) == (128, 1024)
    # no lane-aligned column tile, or no 128-row tile: the kernel's
    # smallest, which ``_use_kernel`` turns into the XLA composition
    assert grouped_gemm._pick_tiles(512, 64, 96, 4) == (128, 128)
    assert grouped_gemm._pick_tiles(100, 64, 128, 4) == (128, 128)
    # float32 blocks are twice the bytes
    assert grouped_gemm._pick_tiles(512, 2048, 1536, 64, 4) == (128, 512)


def test_forward_and_backward_run_at_different_tiles(monkeypatch):
    """Forward at (128, 256) (64 rows a group), backward at (512, 256):
    values and both gradients against ``gmm_reference``, and the backward
    kernels are handed the backward's tiles, not the forward's."""
    m, k, n, e = 512, 64, 256, 8
    fwd = grouped_gemm._pick_tiles(m, k, n, e)
    bwd = grouped_gemm._pick_backward_tiles(m, k, n, 4)
    assert fwd == (128, 256) and bwd == (512, 256)
    lhs, rhs, gs = _case(m, k, n, e, [60, 0, 130, 66, 1, 127, 100, 20],
                         seed=36)
    seen = {}
    for fn in ("_gmm_fwd_kernel_call", "_gmm_dlhs_kernel_call",
               "_gmm_drhs_kernel_call"):
        def spy(a, b, g, tile_m, tile_n, interp, fn=fn,
                real=getattr(grouped_gemm, fn)):
            seen[fn] = (tile_m, tile_n)
            return real(a, b, g, tile_m, tile_n, interp)
        monkeypatch.setattr(grouped_gemm, fn, spy)

    def loss(fn):
        return lambda a, b: jnp.sum(fn(a, b) ** 2)

    got = gmm(lhs, rhs, gs, *fwd, True)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(gmm_reference(lhs, rhs, gs)),
                               atol=1e-5, rtol=1e-5)
    assert np.all(np.asarray(got)[504:] == 0)
    gk = jax.grad(loss(lambda a, b: gmm(a, b, gs, *fwd, True)),
                  argnums=(0, 1))(lhs, rhs)
    gr = jax.grad(loss(lambda a, b: gmm_reference(a, b, gs)),
                  argnums=(0, 1))(lhs, rhs)
    for g, want in zip(gk, gr):
        np.testing.assert_allclose(np.asarray(g), np.asarray(want),
                                   atol=1e-4, rtol=1e-4)
    assert np.all(np.asarray(gk[1])[1] == 0)        # the empty expert
    assert seen == {"_gmm_fwd_kernel_call": fwd,
                    "_gmm_dlhs_kernel_call": bwd,
                    "_gmm_drhs_kernel_call": bwd}


def test_whole_n_under_its_own_limit_runs():
    """Moonlight's gate / up pick, (128, 1408) at K = 2048, is over the
    default budget: the forward call brings ``vmem_limit_bytes`` and gives
    the reference's values (two experts, one of them on a shared tile)."""
    gg = grouped_gemm
    m, k, n, e = 256, 2048, 1408, 2
    assert gg._pick_tiles(m, k, n, e) == (128, n)
    assert gg._VMEM_BUDGET < gg._forward_vmem(128, k, n) \
        <= gg._VMEM_RAISED_BUDGET
    # over the default budget at two slots: the ring takes no third
    assert gg._ring_slots(128, k, n) == 2
    lhs, rhs, gs = _case(m, k, n, e, [150, 90], seed=37, dtype=jnp.bfloat16)
    got = gmm(lhs, rhs, gs, 128, n, True).astype(jnp.float32)
    want = gmm_reference(lhs, rhs, gs).astype(jnp.float32)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=0.05, rtol=0.02)
    assert np.all(np.asarray(got)[240:] == 0)
