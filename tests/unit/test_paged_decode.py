"""Manual-DMA paged decode kernel vs the XLA gather/dense reference
(reference: inference/v2/kernels/ragged_ops/blocked_flash — the decode
hot path).  The kernel is the engine's decode default for 128-aligned
head dims; these run it through the Pallas interpreter on CPU so the
exact kernel code (dynamic live-block walk, double-buffered DMAs,
pad-slot handling, sliding window) is covered off-chip too."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.inference.v2.kernels.blocked_flash import (
    paged_decode_attention)
from deepspeed_tpu.inference.v2.model_implementations.ragged_llama import (
    _paged_attention)

BS = 128


def _setup(seed, S=4, B=4, hkv=2, d=128, dtype=jnp.float32):
    pool_rows = (S * B + 1) * BS
    ks = jax.random.split(jax.random.key(seed), 3)
    k_pool = jax.random.normal(ks[0], (pool_rows, hkv, d), dtype)
    v_pool = jax.random.normal(ks[1], (pool_rows, hkv, d), dtype)
    # distinct non-trash blocks per sequence, deliberately NON-contiguous
    rng = np.random.default_rng(seed)
    perm = rng.permutation(S * B) + 1
    tables = jnp.asarray(perm.reshape(S, B), jnp.int32)
    q = jax.random.normal(ks[2], (S, 8, d), dtype)
    return q, k_pool, v_pool, tables


@pytest.mark.parametrize("window", [None, 100])
def test_paged_decode_matches_reference(window):
    q, k_pool, v_pool, tables = _setup(0)
    token_pos = jnp.asarray([200, 317, 64, 450], jnp.int32)
    token_slot = jnp.arange(4, dtype=jnp.int32)
    batch = {"block_tables": tables, "token_slot": token_slot,
             "token_pos": token_pos}
    got = paged_decode_attention(q, k_pool, v_pool, tables, token_slot,
                                 token_pos, block_size=BS, window=window,
                                 interpret=True)
    want = _paged_attention(q, k_pool, v_pool, batch, BS,
                            use_kernel=False, window=window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=5e-3, rtol=1e-2)
    # the window must actually bite on the long-context rows
    if window is not None:
        full = _paged_attention(q, k_pool, v_pool, batch, BS,
                                use_kernel=False)
        assert float(jnp.max(jnp.abs(want[0] - full[0]))) > 1e-3


def test_paged_decode_pad_slots_zero_and_block_boundary():
    q, k_pool, v_pool, tables = _setup(1)
    # pos = -1 marks a pad slot; pos = BS-1 / BS exercise the block edge
    token_pos = jnp.asarray([BS - 1, BS, -1, 2 * BS], jnp.int32)
    token_slot = jnp.arange(4, dtype=jnp.int32)
    batch = {"block_tables": tables, "token_slot": token_slot,
             "token_pos": token_pos}
    got = paged_decode_attention(q, k_pool, v_pool, tables, token_slot,
                                 token_pos, block_size=BS,
                                 interpret=True)
    assert float(jnp.max(jnp.abs(got[2]))) == 0.0       # pad row
    want = _paged_attention(q, k_pool, v_pool, batch, BS,
                            use_kernel=False)
    for i in (0, 1, 3):
        np.testing.assert_allclose(np.asarray(got[i]),
                                   np.asarray(want[i]),
                                   atol=5e-3, rtol=1e-2)


def test_paged_decode_gqa_grouping():
    """8 q heads over 2 kv heads: head h must read kv head h//4."""
    q, k_pool, v_pool, tables = _setup(2)
    token_pos = jnp.full((4,), 300, jnp.int32)
    token_slot = jnp.arange(4, dtype=jnp.int32)
    batch = {"block_tables": tables, "token_slot": token_slot,
             "token_pos": token_pos}
    got = paged_decode_attention(q, k_pool, v_pool, tables, token_slot,
                                 token_pos, block_size=BS,
                                 interpret=True)
    want = _paged_attention(q, k_pool, v_pool, batch, BS,
                            use_kernel=False)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=5e-3, rtol=1e-2)


@pytest.mark.parametrize("hkv", [2, 8])
@pytest.mark.parametrize("window", [None, 100])
def test_paged_decode_and_verify_int8_match_dequantized_reference(hkv,
                                                                   window):
    """int8 pools: the kernels walk the flattened-lane [bs, Hkv*D] payload
    and apply the pre-gathered [Hkv, bs] scales around the dots (the
    layout Mosaic compiles; the chip's selftest runs the same cases
    compiled).  Same pools, same scales as the XLA path over explicitly
    dequantized pools, so any difference is the kernel's arithmetic."""
    from deepspeed_tpu.inference.v2.kernels.blocked_flash import (
        paged_verify_attention)
    from deepspeed_tpu.inference.v2.ragged.kv_cache import (dequantize_kv,
                                                            quantize_kv)

    q, k_pool, v_pool, tables = _setup(3, hkv=hkv)
    kq, ks = quantize_kv(k_pool)
    vq, vs = quantize_kv(v_pool)
    kd = dequantize_kv(kq, ks, jnp.float32)
    vd = dequantize_kv(vq, vs, jnp.float32)
    token_pos = jnp.asarray([200, 317, -1, 450], jnp.int32)
    token_slot = jnp.arange(4, dtype=jnp.int32)
    batch = {"block_tables": tables, "token_slot": token_slot,
             "token_pos": token_pos}
    got = paged_decode_attention(q, kq, vq, tables, token_slot, token_pos,
                                 block_size=BS, window=window,
                                 k_scale=ks, v_scale=vs, interpret=True)
    want = _paged_attention(q, kd, vd, batch, BS, use_kernel=False,
                            window=window)
    assert float(jnp.max(jnp.abs(got[2]))) == 0.0       # pad row
    for i in (0, 1, 3):
        np.testing.assert_allclose(np.asarray(got[i]), np.asarray(want[i]),
                                   atol=5e-3, rtol=1e-2)

    K = 4
    qv = jax.random.normal(jax.random.key(9), (4 * K, 8, 128), jnp.float32)
    vslot = jnp.repeat(token_slot, K)
    vpos = (jnp.asarray([200, 317, 64, 450], jnp.int32)[:, None]
            + jnp.arange(K, dtype=jnp.int32)[None, :]).reshape(-1)
    vbatch = {"block_tables": tables, "token_slot": vslot,
              "token_pos": vpos}
    gotv = paged_verify_attention(qv, kq, vq, tables, vslot, vpos,
                                  block_size=BS, k_tokens=K, window=window,
                                  k_scale=ks, v_scale=vs, interpret=True)
    wantv = _paged_attention(qv, kd, vd, vbatch, BS, use_kernel=False,
                             window=window)
    np.testing.assert_allclose(np.asarray(gotv), np.asarray(wantv),
                               atol=5e-3, rtol=1e-2)
