"""One K/V cache per (layer, pass) behind one allocator and one block table
(a model's ``kv_passes``), on the host and the device alone: no model runs.

What is checked: a pool is ``passes`` times as long and a token costs
``passes x layers x`` one cache layer's bytes (1.5 MiB at the published
sizes of the looped model served); ``copy_block`` / ``gather_blocks`` /
``scatter_blocks`` move a block's rows in EVERY pass and nothing else, with
a payload that still splits by block; the occupancy gauges count every
pass; without ``kv_passes`` nothing changes."""

from __future__ import annotations

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.inference.v2.config_v2 import (DSStateManagerConfig,
                                                  KVCacheConfig)
from deepspeed_tpu.inference.v2.ragged import BlockedKVCache, DSStateManager
from deepspeed_tpu.observability.memory import kv_occupancy

PASSES, LAYERS, BLOCKS, BS = 3, 2, 6, 8


def _cache(passes=PASSES, dtype=jnp.float32, heads=2, dim=16):
    kv = BlockedKVCache(LAYERS, BLOCKS, BS, heads, dim, dtype,
                        passes=passes)
    rows = passes * BLOCKS * BS
    # every row holds its own index in every lane: a moved row is seen
    kv.cache = jax.tree.map(
        lambda a: (jnp.arange(rows, dtype=jnp.float32).reshape(
            (rows,) + (1,) * (a.ndim - 1)) + jnp.zeros(a.shape)
        ).astype(a.dtype), kv.cache)
    return kv


def _rows_of(kv, block):
    """Pool rows of ``block``, pass after pass."""
    return np.concatenate([
        np.arange(BS) + block * BS + t * BLOCKS * BS
        for t in range(kv.passes)])


def test_per_token_bytes_at_the_published_sizes():
    kv = BlockedKVCache(48, 2, 8, 16, 128, jnp.bfloat16, passes=4)
    assert kv.layer_token_bytes == 2 * 16 * 128 * 2 == 8192
    assert kv.per_token_bytes == 4 * 48 * 8192 == 1_572_864
    assert kv.block_rows == 4 * 8
    # the flat row, 4 passes of 2 blocks of 8 rows
    assert kv.cache["layer_47"]["k"].shape == (4 * 2 * 8, 2048)
    assert len(kv.cache) == 48
    # and one pass is what it always was
    one = BlockedKVCache(48, 2, 8, 16, 128, jnp.bfloat16)
    assert (one.passes, one.per_token_bytes) == (1, 48 * 8192)
    assert one.cache["layer_0"]["k"].shape == (2 * 8, 2048)


@pytest.mark.parametrize("dtype", [jnp.float32, "int8"])
def test_copy_block_copies_the_rows_of_every_pass(dtype):
    kv = _cache(dtype=jnp.float32)
    if dtype == "int8":
        kv = BlockedKVCache(LAYERS, BLOCKS, BS, 2, 16, "int8", passes=PASSES)
        rows = PASSES * BLOCKS * BS
        kv.cache = jax.tree.map(
            lambda a: (jnp.arange(rows, dtype=jnp.float32).reshape(
                (rows,) + (1,) * (a.ndim - 1)) % 100 + jnp.zeros(a.shape)
            ).astype(a.dtype), kv.cache)
        assert set(kv.cache["layer_0"]) == {"k", "v", "k_scale", "v_scale"}
    before = jax.tree.map(np.asarray, kv.cache)
    kv.copy_block(2, 5)
    src, dst = _rows_of(kv, 2), _rows_of(kv, 5)
    for name, leaves in kv.cache.items():
        for leaf, a in leaves.items():
            a, b = np.asarray(a), before[name][leaf]
            np.testing.assert_array_equal(a[dst], b[src])
            keep = np.setdiff1d(np.arange(a.shape[0]), dst)
            np.testing.assert_array_equal(a[keep], b[keep])


def test_gather_and_scatter_round_trip_a_block_in_every_pass():
    kv = _cache()
    payload = kv.gather_blocks([4, 1])
    k = payload["layer_1"]["k"]
    assert k.shape == (2 * kv.block_rows, 2, 16)
    k = k.reshape(k.shape[0], -1)
    # block-major: table entry i is rows [i, i + 1) x block_rows, pass t of
    # it from t x block_size: a payload splits by block
    np.testing.assert_array_equal(
        k[:, 0], np.concatenate([_rows_of(kv, 4), _rows_of(kv, 1)]))
    other = BlockedKVCache(LAYERS, BLOCKS, BS, 2, 16, jnp.float32,
                           passes=PASSES)
    other.scatter_blocks([3, 5], payload)
    got = np.asarray(other.cache["layer_1"]["k"])[:, 0, 0]
    np.testing.assert_array_equal(got[_rows_of(kv, 3)], _rows_of(kv, 4))
    np.testing.assert_array_equal(got[_rows_of(kv, 5)], _rows_of(kv, 1))
    untouched = np.setdiff1d(np.arange(got.shape[0]), np.concatenate(
        [_rows_of(kv, 3), _rows_of(kv, 5)]))
    assert not got[untouched].any()
    # one block of the payload alone, as the host tier keeps it
    part = jax.tree.map(lambda a: a[kv.block_rows:], payload)
    other.scatter_blocks([2], part)
    np.testing.assert_array_equal(
        np.asarray(other.cache["layer_0"]["v"])[_rows_of(kv, 2), 0, 0],
        _rows_of(kv, 1))
    with pytest.raises(ValueError, match="cache geometry differs"):
        BlockedKVCache(LAYERS, BLOCKS, BS, 2, 16, jnp.float32).scatter_blocks(
            [2], part)


def _manager(**stated):
    return DSStateManager(
        DSStateManagerConfig(max_ragged_batch_size=32,
                             max_ragged_sequence_count=2, max_context=64),
        KVCacheConfig(block_size=BS, num_blocks=BLOCKS),
        num_layers=LAYERS, num_kv_heads=2, head_dim=16, dtype=jnp.float32,
        **stated)


def test_the_manager_builds_the_passes_and_the_gauges_count_them():
    sm, plain = _manager(kv_passes=PASSES), _manager()
    assert sm.kv_cache.passes == PASSES and plain.kv_cache.passes == 1
    assert sm.allocator.num_blocks == plain.allocator.num_blocks == BLOCKS
    assert sm.kv_cache.per_token_bytes == PASSES * plain.kv_cache.per_token_bytes
    assert sm.unserved == {} and plain.unserved == {}
    seq = sm.get_or_create_sequence(1)
    sm.maybe_allocate_kv(seq, 20)
    seq.seen_tokens = 20
    assert len(seq.blocks) == 3                 # ONE table, whatever passes
    g, p = kv_occupancy(sm), kv_occupancy(plain)
    assert g["observability/kv_blocks_live"] == 3
    assert g["observability/kv_pool_bytes"] \
        == PASSES * p["observability/kv_pool_bytes"]
    assert g["observability/kv_live_bytes"] \
        == 3 * BS * PASSES * LAYERS * 2 * 2 * 16 * 4


def test_a_window_group_beside_the_passes_is_refused():
    from deepspeed_tpu.inference.v2.ragged import CacheLayoutError

    with pytest.raises(CacheLayoutError, match="kv_passes beside kv_groups"):
        _manager(kv_passes=PASSES,
                 kv_groups={"window": {"layers": [0], "window": 16}})
