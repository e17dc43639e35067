"""``ops/selective_scan.py``: both Mosaic kernels in interpret mode against
their XLA compositions and against a plain token-by-token loop in numpy,
with a reset, rows on the scratch slot and a tile's tail of pad rows."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.ops import selective_scan as ss

N, SLOTS = 4, 5          # state indices; live slots (slot 5 is scratch)


def _inputs(rows, di, seed):
    rng = np.random.default_rng(seed)
    f = lambda *s: rng.standard_normal(s).astype(np.float32)
    dt = np.log1p(np.exp(f(rows, di) - 2.0))
    return (f(SLOTS + 1, N, di), dt, dt * f(rows, di), f(rows, N),
            f(rows, N), -np.exp(f(N, di)))


def _loop(pool, dt, dtx, b, c, a, row_slot, row_reset):
    """One token after another; ``row_slot`` / ``row_reset`` a row."""
    pool, y = pool.copy(), np.zeros_like(dt)
    for t in range(dt.shape[0]):
        s = pool[row_slot[t]] * (0.0 if row_reset[t] else 1.0)
        s = np.exp(dt[t][None, :] * a) * s + dtx[t][None, :] * b[t][:, None]
        y[t] = (s * c[t][:, None]).sum(0)
        pool[row_slot[t]] = s
    return y, pool


@pytest.mark.parametrize("di", [128, 384, 200],
                         ids=["one_block", "three_blocks", "no_lane_tile"])
def test_step_kernel_composition_and_loop_agree(di):
    pool, dt, dtx, b, c, a = _inputs(8, di, 1)
    slots = np.asarray([1, 0, SLOTS, 3, SLOTS, SLOTS, 2, SLOTS], np.int32)
    reset = np.asarray([0, 1, 1, 0, 0, 0, 0, 0], bool)
    # pad rows (the scratch slot's) carry dt = 0
    pad = slots == SLOTS
    dt[pad], dtx[pad] = 0.0, 0.0
    want_y, want_pool = _loop(pool, dt, dtx, b, c, a, slots, reset)
    args = tuple(map(jnp.asarray, (pool, dt, dtx, b, c, a, slots, reset)))
    for interpret in (None, True):
        y, new = ss.ssm_step(*args, interpret=interpret)
        np.testing.assert_allclose(np.asarray(y)[~pad], want_y[~pad],
                                   rtol=2e-5, atol=2e-6)
        np.testing.assert_allclose(np.asarray(new)[:SLOTS],
                                   want_pool[:SLOTS], rtol=2e-5, atol=2e-6)
        # the slot no row names is bitwise as it was
        assert np.array_equal(np.asarray(new)[4], pool[4])


@pytest.mark.parametrize("di, tile", [(128, 16), (640, 8), (128, 4)],
                         ids=["one_block", "five_blocks",
                              "tile_under_the_unroll"])
def test_chunk_kernel_composition_and_loop_agree(di, tile):
    # three sequences over five tiles (2 + 2 + 1, the last 5 rows short of
    # its tile), a pad tile behind them; the first starts at position 0
    rows = 6 * tile
    pool, dt, dtx, b, c, a = _inputs(rows, di, 2)
    real = np.arange(rows) < 5 * tile - min(5, tile - 1)
    dt[~real], dtx[~real] = 0.0, 0.0
    tile_slot = np.asarray([2, 2, 4, 4, 0, SLOTS], np.int32)
    tile_reset = np.asarray([1, 0, 0, 0, 1, 0], bool)
    row_slot = np.repeat(tile_slot, tile)
    row_reset = np.repeat(tile_reset, tile) & (np.arange(rows) % tile == 0)
    want_y, want_pool = _loop(pool, dt, dtx, b, c, a, row_slot, row_reset)
    args = tuple(map(jnp.asarray, (pool, dt, dtx, b, c, a, tile_slot,
                                   tile_reset)))
    for interpret in (None, True):
        y, new = ss.ssm_chunk(*args, tile, interpret=interpret)
        np.testing.assert_allclose(np.asarray(y)[real], want_y[real],
                                   rtol=5e-5, atol=5e-6)
        np.testing.assert_allclose(np.asarray(new)[:SLOTS],
                                   want_pool[:SLOTS], rtol=5e-5, atol=5e-6)
        for untouched in (1, 3):
            assert np.array_equal(np.asarray(new)[untouched],
                                  pool[untouched])


def test_pad_rows_leave_a_state_bit_equal():
    """``dt = 0``: decay exp(0) = 1 and input 0, so a tile's tail of pad
    rows (and a whole pad tile on a LIVE slot) changes nothing."""
    pool, dt, dtx, b, c, a = _inputs(32, 128, 3)
    dt[:], dtx[:] = 0.0, 0.0
    args = tuple(map(jnp.asarray, (pool, dt, dtx, b, c, a)))
    for interpret in (None, True):
        _, new = ss.ssm_chunk(*args, jnp.asarray([1, 3], jnp.int32),
                              jnp.zeros((2,), bool), 16, interpret=interpret)
        assert np.array_equal(np.asarray(new), pool)
        _, new = ss.ssm_step(*(x[:4] if i in (1, 2, 3, 4) else x
                               for i, x in enumerate(args)),
                             jnp.asarray([0, 1, 2, 3], jnp.int32),
                             jnp.zeros((4,), bool), interpret=interpret)
        assert np.array_equal(np.asarray(new), pool)


def test_channel_blocks():
    assert ss._channel_block(5120, ss.STEP_BLOCK) == 5120
    assert ss._channel_block(5120, ss.CHUNK_BLOCK) == 512
    assert ss._channel_block(640, 512) == 128
    assert ss._channel_block(200, 512) == 200       # interpret mode's sizes


def test_no_tokens_by_state_tensor_in_the_chunk_program():
    """The lowered chunk call holds no ``[T, N, Di]`` operand or result: the
    decay is formed inside the kernel."""
    t, di, tile = 64, 256, 16
    pool, dt, dtx, b, c, a = map(jnp.asarray, _inputs(t, di, 4))
    text = jax.jit(lambda *x: ss.ssm_chunk(*x, tile, interpret=False)).trace(
        pool, dt, dtx, b, c, a, jnp.zeros((4,), jnp.int32),
        jnp.zeros((4,), bool)).lower(lowering_platforms=("tpu",)).as_text()
    assert "tpu_custom_call" in text
    assert f"{t}x{N}x{di}" not in text and f"{t}x{di}x{N}" not in text
