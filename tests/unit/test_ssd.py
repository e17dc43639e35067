"""``ops/ssd.py`` (Mamba-2's recurrence over state slots): both Mosaic
kernels in interpret mode and both XLA compositions against a plain NumPy
loop, token after token, of each sequence's whole history."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops import ssd

F32 = np.float32


def _inputs(rows, h, p, n, seed=0, slots=5):
    rng = np.random.default_rng(seed)
    f = lambda *shape: rng.standard_normal(shape).astype(F32)
    dt = np.log1p(np.exp(f(rows, h) - 2.0))
    a = -np.exp(0.5 * f(h))
    x = f(rows, h * p)
    return {"pool": f(slots + 1, n, h * p), "da": dt * a,
            "dtx": np.repeat(dt, p, axis=1) * x, "b": f(rows, n),
            "c": f(rows, n)}


def _loop(state, da, dtx, b, c):
    """One sequence, token after token.  state [N, Di] float64."""
    p = dtx.shape[1] // da.shape[1]
    ys = []
    for t in range(dtx.shape[0]):
        state = np.repeat(np.exp(da[t].astype(np.float64)), p)[None, :] \
            * state + b[t][:, None].astype(np.float64) * dtx[t][None, :]
        ys.append(c[t].astype(np.float64) @ state)
    return state, np.stack(ys)


@pytest.mark.parametrize("interpret", [None, True], ids=["xla", "kernel"])
@pytest.mark.parametrize("h, p, n", [(4, 16, 8), (2, 64, 16), (4, 64, 8),
                                     (1, 128, 8), (2, 256, 8)])
def test_step_matches_the_token_loop(interpret, h, p, n):
    i = _inputs(8, h, p, n)
    slots = np.asarray([1, 0, 5, 3, 5, 5, 2, 5], np.int32)
    reset = np.asarray([0, 1, 0, 0, 0, 0, 1, 0], bool)
    # pad rows: dt = 0, the scratch slot
    pad = slots == 5
    i["da"][pad] = 0.0
    i["dtx"][pad] = 0.0
    y, pool = ssd.ssd_step(*(jnp.asarray(i[k]) for k in
                             ("pool", "da", "dtx", "b", "c")),
                           jnp.asarray(slots), jnp.asarray(reset),
                           interpret=interpret)
    for r in np.flatnonzero(~pad):
        s0 = np.zeros_like(i["pool"][0], np.float64) if reset[r] \
            else i["pool"][slots[r]].astype(np.float64)
        s1, want = _loop(s0, i["da"][r:r + 1], i["dtx"][r:r + 1],
                         i["b"][r:r + 1], i["c"][r:r + 1])
        np.testing.assert_allclose(y[r], want[0], rtol=2e-5, atol=2e-5)
        np.testing.assert_allclose(pool[slots[r]], s1, rtol=2e-5, atol=2e-5)
    # no other slot changed; the scratch slot kept its content (dt = 0)
    for s in (4, 5):
        np.testing.assert_array_equal(pool[s], i["pool"][s])


@pytest.mark.parametrize("interpret", [None, True], ids=["xla", "kernel"])
@pytest.mark.parametrize("h, p, n, tile", [(4, 16, 8, 16), (2, 64, 16, 32),
                                           (4, 64, 128, 128), (1, 128, 8, 16),
                                           (2, 256, 8, 16)])
def test_chunk_matches_the_token_loop(interpret, h, p, n, tile):
    """Three sequences' tiles in one call: one of three tiles whose state
    is carried from tile to tile, one that starts at position 0 in a slot
    another sequence held (reset), one pad tile on the scratch slot; the
    last tile of the first sequence has a padded tail."""
    nt = 6
    i = _inputs(nt * tile, h, p, n, seed=1)
    tile_slot = np.asarray([2, 2, 2, 0, 5, 3], np.int32)
    tile_reset = np.asarray([0, 0, 0, 1, 0, 0], bool)
    live = np.ones(nt * tile, bool)
    live[3 * tile - tile // 4:3 * tile] = False       # a padded tail
    live[4 * tile:5 * tile] = False                   # a pad tile
    i["da"][~live] = 0.0
    i["dtx"][~live] = 0.0
    y, pool = ssd.ssd_chunk(*(jnp.asarray(i[k]) for k in
                              ("pool", "da", "dtx", "b", "c")),
                            jnp.asarray(tile_slot), jnp.asarray(tile_reset),
                            tile, interpret=interpret)
    tol = dict(rtol=1e-4, atol=1e-4)
    for slot, rows, zero in ((2, slice(0, 3 * tile), False),
                             (0, slice(3 * tile, 4 * tile), True),
                             (3, slice(5 * tile, 6 * tile), False)):
        s0 = np.zeros_like(i["pool"][0], np.float64) if zero \
            else i["pool"][slot].astype(np.float64)
        s1, want = _loop(s0, i["da"][rows], i["dtx"][rows], i["b"][rows],
                         i["c"][rows])
        keep = live[rows]
        np.testing.assert_allclose(np.asarray(y[rows])[keep], want[keep],
                                   **tol)
        np.testing.assert_allclose(pool[slot], s1, **tol)
    for s in (1, 4, 5):
        np.testing.assert_array_equal(pool[s], i["pool"][s])


@pytest.mark.parametrize("interpret", [None, True], ids=["xla", "kernel"])
@pytest.mark.parametrize("cuts", [(96,), (32, 64), (16, 48, 80)])
def test_a_prompt_chunked_at_any_boundary_equals_the_unchunked_one(
        interpret, cuts):
    """96 tokens in one call of six tiles, and the same tokens cut into
    several calls (then one-token steps for the last 8): the state goes
    through the pool between calls."""
    h, p, n, tile, total = 4, 16, 8, 16, 96
    i = _inputs(total + 8, h, p, n, seed=2)
    a = {k: jnp.asarray(v) for k, v in i.items()}

    def run(bounds):
        pool, ys, lo = a["pool"], [], 0
        for hi in bounds:
            nt = (hi - lo) // tile
            y, pool = ssd.ssd_chunk(
                pool, a["da"][lo:hi], a["dtx"][lo:hi], a["b"][lo:hi],
                a["c"][lo:hi], jnp.full((nt,), 1, jnp.int32),
                jnp.asarray([lo == 0] + [False] * (nt - 1)), tile,
                interpret=interpret)
            ys.append(y)
            lo = hi
        for t in range(total, total + 8):
            y, pool = ssd.ssd_step(
                pool, a["da"][t:t + 1], a["dtx"][t:t + 1], a["b"][t:t + 1],
                a["c"][t:t + 1], jnp.asarray([1]), jnp.asarray([False]),
                interpret=interpret)
            ys.append(y)
        return np.concatenate(ys), np.asarray(pool)

    y1, pool1 = run((total,))
    y2, pool2 = run(cuts + ((total,) if cuts[-1] != total else ()))
    np.testing.assert_allclose(y2, y1, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(pool2, pool1, rtol=1e-4, atol=1e-4)
    s1, want = _loop(np.zeros((n, h * p)), i["da"], i["dtx"], i["b"],
                     i["c"])
    np.testing.assert_allclose(y1, want, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(pool1[1], s1, rtol=1e-4, atol=1e-4)


def test_strong_decay_inside_a_chunk_stays_finite():
    """``dt A`` of -40 a token: a ratio of cumulative products would be
    0 / 0 after three tokens; differences of the sums are exact."""
    h, p, n, tile = 2, 64, 8, 32
    i = _inputs(tile, h, p, n, seed=3)
    i["da"][:] = -40.0
    y, pool = ssd.ssd_chunk(*(jnp.asarray(i[k]) for k in
                              ("pool", "da", "dtx", "b", "c")),
                            jnp.asarray([1]), jnp.asarray([False]), tile,
                            interpret=True)
    assert np.isfinite(np.asarray(y)).all()
    _, want = _loop(i["pool"][1].astype(np.float64), i["da"], i["dtx"],
                    i["b"], i["c"])
    np.testing.assert_allclose(y, want, rtol=1e-4, atol=1e-4)


def test_widths_the_lane_groups_cannot_hold_are_refused_by_name():
    i = _inputs(8, 3, 48, 8)                  # 48 divides no lane tile
    with pytest.raises(ValueError, match="lane tile"):
        ssd.ssd_step(*(jnp.asarray(i[k]) for k in
                       ("pool", "da", "dtx", "b", "c")),
                     jnp.zeros((8,), jnp.int32), jnp.zeros((8,), bool),
                     interpret=True)


def test_the_cells_shape_lowers_for_the_tpu():
    """Granite-4.0-H-Small's layer (128 heads x 64, 128 states): both calls
    lower to Mosaic under the kernels' names, the pool aliased."""
    h, p, n, tile = 128, 64, 128, 128
    sds = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32)
    ints = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)
    step = jax.jit(lambda *a: ssd._ssd_step_call(*a, 2048, False)).trace(
        sds(9, n, h * p), sds(16, h), sds(16, h * p), sds(16, n), sds(16, n),
        ints(16), ints(16)).lower(lowering_platforms=("tpu",)).as_text()
    chunk = jax.jit(lambda *a: ssd._ssd_chunk_call(
        *a, tile, 1024, False)).trace(
        sds(9, n, h * p), sds(256, h), sds(256, h * p), sds(256, n),
        sds(256, n), ints(2), ints(2)).lower(
            lowering_platforms=("tpu",)).as_text()
    for text, name in ((step, "_ssd_step_kernel"),
                       (chunk, "_ssd_chunk_kernel")):
        assert "tpu_custom_call" in text and name in text
        assert "output_operand_aliases" in text or "operand_index" in text
