"""Manual-DMA paged decode kernel vs the XLA gather/dense reference
(reference: inference/v2/kernels/ragged_ops/blocked_flash — the decode
hot path).  The kernel is the read of every one-token row at 128-aligned
head dims (a decode step's rows, the single-token segment of a tiled
``put`` program), whatever the pool's size, on the flat pool row
[rows, Hkv*D] a float pool is stored in; these run it through the
Pallas interpreter on CPU so the exact kernel code (dynamic walk over the
held blocks, the DMA schedule that runs from one row into the next,
pad-row handling, sliding window, several table entries a step) is covered
off-chip too."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.inference.v2.kernels.blocked_flash import (
    paged_decode_attention)
from deepspeed_tpu.inference.v2.modules.attention import (
    _paged_attention)

BS = 128


def _setup(seed, S=4, B=4, hkv=2, d=128, dtype=jnp.float32, flat=True):
    """``flat``: the pool in the flat row a float pool is stored in, else
    [rows, Hkv, D] (what an int8 pool is quantized from)."""
    pool_rows = (S * B + 1) * BS
    ks = jax.random.split(jax.random.key(seed), 3)
    row = (hkv * d,) if flat else (hkv, d)
    k_pool = jax.random.normal(ks[0], (pool_rows,) + row, dtype)
    v_pool = jax.random.normal(ks[1], (pool_rows,) + row, dtype)
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

    q, k_pool, v_pool, tables = _setup(3, hkv=hkv, flat=False)
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


# The head layouts of the three serving cells (Mistral-7B, OLMoE,
# Qwen3-Next): KV heads, group size, head size.
CELL_HEADS = [(8, 4, 128), (16, 1, 128), (2, 8, 256)]

# One-token rows as a ``put`` program's single-token segment and a decode
# step have them.  Each case: (token_slot, token_pos, window, rows of the
# table that share their first block).
ROW_CASES = {
    # slots in no order, pad rows (position -1) in between
    "permuted_with_pads": ([3, 0, 0, 1, 0, 2], [200, -1, -1, 317, -1, 64],
                           None, ()),
    # a row ending exactly on a block edge, a pad row, a live row: the
    # step after the first row's last is the THIRD row's first
    "edge_pad_live": ([0, 0, 1, 2], [BS - 1, -1, 2 * BS, 3 * BS - 1],
                      None, ()),
    # pads first and last: the first copy of the call is row 2's, and
    # nothing is started after the last live row
    "pads_at_both_ends": ([0, 0, 2, 1, 0], [-1, -1, 450, BS, -1], None, ()),
    # sliding window whose first block is not the table's first (lo > 0)
    "window_lo_positive": ([0, 1, 2, 3], [200, 450, 300, 130], 100, ()),
    # two rows hold the same block (prefix sharing)
    "shared_prefix_block": ([0, 1, 2, 3], [300, 270, 64, 129], None, (0, 1)),
    # no live row at all
    "all_pads": ([0, 0, 0, 0], [-1, -1, -1, -1], None, ()),
}


@pytest.mark.parametrize("case", sorted(ROW_CASES))
@pytest.mark.parametrize("hkv,g,d", CELL_HEADS)
def test_walk_matches_xla_read_on_a_tight_pool(hkv, g, d, case):
    """The three cells' head layouts at a pool SMALLER than the table
    extent (S x B blocks of table, 3 x B of pool: what the removed
    ``big_pool`` rule sent to the dense read), bf16 as in the cells,
    against ``_paged_attention(use_kernel=False)``."""
    slot, pos, window, shared = ROW_CASES[case]
    S, B = 4, 4
    nb = 3 * B
    rng = np.random.default_rng(7)
    ks = jax.random.split(jax.random.key(11), 3)
    k_pool = jax.random.normal(ks[0], (nb * BS, hkv * d), jnp.bfloat16)
    v_pool = jax.random.normal(ks[1], (nb * BS, hkv * d), jnp.bfloat16)
    # a tight pool cannot give every table entry a block of its own: the
    # entries a row can reach (its position's block and those before it)
    # get distinct blocks, the rest name block 0 as a fresh table does
    tables = np.zeros((S, B), np.int32)
    free = list(rng.permutation(nb - 1) + 1)
    need = {s_: 0 for s_ in range(S)}
    for s_, p_ in zip(slot, pos):
        need[s_] = max(need[s_], p_ // BS + 1)
    for s_, n in need.items():
        tables[s_, :n] = [free.pop() for _ in range(n)]
    for s_ in shared[1:]:
        tables[s_, 0] = tables[shared[0], 0]
    tables = jnp.asarray(tables)
    assert k_pool.shape[0] < S * B * BS
    q = jax.random.normal(ks[2], (len(pos), hkv * g, d), jnp.bfloat16)
    slot = jnp.asarray(slot, jnp.int32)
    pos = jnp.asarray(pos, jnp.int32)
    batch = {"block_tables": tables, "token_slot": slot, "token_pos": pos}
    got = paged_decode_attention(q, k_pool, v_pool, tables, slot, pos,
                                 block_size=BS, window=window,
                                 interpret=True)
    want = _paged_attention(q, k_pool, v_pool, batch, BS, use_kernel=False,
                            window=window)
    live = np.asarray(pos) >= 0
    got, want = (np.asarray(x.astype(jnp.float32)) for x in (got, want))
    assert np.all(got[~live] == 0.0)
    np.testing.assert_allclose(got[live], want[live], atol=2e-2, rtol=2e-2)
    if window is not None:          # the window must bite somewhere
        full = np.asarray(_paged_attention(
            q, k_pool, v_pool, batch, BS, use_kernel=False
        ).astype(jnp.float32))
        assert np.abs(want - full).max() > 1e-2


@pytest.mark.parametrize("mode", ["decode_step", "put_single_rows"])
def test_one_token_rows_lower_to_the_walk_on_a_tight_pool(monkeypatch, mode):
    """With the kernel route forced and a pool under the table extent, a
    decode step and the single-token segment of a tiled ``put`` program
    lower (for the TPU) to the Mosaic call of ``_decode_kernel`` inside
    the scope ``attn/dense_read``, and to no dot of the XLA dense read."""
    import re

    from deepspeed_tpu.inference.v2.kernels import blocked_flash

    monkeypatch.setattr(blocked_flash, "on_tpu", lambda: True)
    S, B, hkv, g, d, tile = 4, 5, 8, 4, 128, 128    # shapes no test runs
    nb = S * B // 2
    q_rows = S if mode == "decode_step" else S + tile
    args = (jax.ShapeDtypeStruct((q_rows, hkv * g, d), jnp.bfloat16),
            jax.ShapeDtypeStruct((nb * BS, hkv * d), jnp.bfloat16),
            jax.ShapeDtypeStruct((nb * BS, hkv * d), jnp.bfloat16),
            jax.ShapeDtypeStruct((S, B), jnp.int32),
            jax.ShapeDtypeStruct((q_rows,), jnp.int32),
            jax.ShapeDtypeStruct((q_rows,), jnp.int32))

    def read(q, kp, vp, tables, slot, pos):
        batch = {"block_tables": tables, "token_slot": slot,
                 "token_pos": pos}
        how = {"decode_mode": True} if mode == "decode_step" else \
            {"prefill_tile": tile}
        return _paged_attention(q, kp, vp, batch, BS, use_kernel=True,
                                **how)

    text = jax.jit(read).trace(*args).lower(
        lowering_platforms=("tpu",)).as_text(debug_info=True)
    kernels = re.findall(r'@tpu_custom_call\(.*?kernel_name = "([^"]+)"',
                         text)
    assert kernels.count("_decode_kernel") == 1
    assert set(kernels) <= {"_decode_kernel", "_prefill_kernel"}
    assert "attn/dense_read/jit(paged_decode_attention)" in text
    assert "dot_general" not in text and "attn/gather_read" not in text
    # the pool reaches both kernels as it is stored: split into blocks,
    # never transposed or re-laid (what the [rows, Hkv, D] form cost)
    assert "stablehlo.transpose" not in text
    assert not re.search(r"stablehlo\.reshape.*tensor<%dx%dx%dx" % (
        nb, BS, hkv), text)
