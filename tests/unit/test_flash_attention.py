"""Flash-attention kernel numerics vs the XLA composition (the reference's
kernel-vs-eager-torch test pattern, tests/unit/ops/ — SURVEY §4).

Runs the real Pallas kernels through the interpreter on CPU, so the exact
TPU kernel code is exercised by the suite.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops.attention import _xla_attention, dot_product_attention
from deepspeed_tpu.ops.flash_attention import (flash_attention,
                                               flash_attention_usable)


def _make(b=2, sq=256, sk=256, h=4, hkv=4, d=64, dtype=jnp.float32, seed=0):
    kq, kk, kv = jax.random.split(jax.random.key(seed), 3)
    q = jax.random.normal(kq, (b, sq, h, d), dtype)
    k = jax.random.normal(kk, (b, sk, hkv, d), dtype)
    v = jax.random.normal(kv, (b, sk, hkv, d), dtype)
    return q, k, v


#: (q heads, kv heads, head size).  The first three are this file's own; the
#: rest are what the folded and paired families' tests held until PR 54
#: took the families out: GPT-2's 12 x 64, GQA groups of 2 and 3 at 64
#: lanes, 32-lane heads, and 128-lane heads with and without a group.
GEOMS = [(4, 4, 64), (4, 2, 64), (4, 1, 64), (12, 12, 64), (8, 4, 64),
         (6, 2, 64), (4, 4, 32), (4, 4, 128), (4, 2, 128)]
#: None: the defaults (one tile at 256 x 256, the one-pass forward);
#: (64, 128): four q-tiles by two k-tiles, the forward with its scratch
BLOCKS = [None, (64, 128)]
_ids = lambda g: "-".join(map(str, g)) if g else "default"


@pytest.mark.parametrize("blocks", BLOCKS, ids=_ids)
@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("geom", GEOMS, ids=_ids)
def test_flash_forward_matches_xla(geom, causal, blocks):
    h, hkv, d = geom
    bq, bk = blocks or (None, None)
    q, k, v = _make(h=h, hkv=hkv, d=d)
    ref = _xla_attention(q, k, v, causal=causal, mask=None, scale=None)
    out = flash_attention(q, k, v, causal=causal, block_q=bq, block_k=bk,
                          interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def _grads(fn, q, k, v):
    return jax.grad(lambda *a: jnp.sum(fn(*a).astype(jnp.float32) ** 2),
                    argnums=(0, 1, 2))(q, k, v)


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("geom", GEOMS[1:2] + GEOMS[3:], ids=_ids)
def test_flash_grads_match_xla(geom, causal):
    """dQ over two k-tiles and dK/dV over four q-tiles, group-summed."""
    h, hkv, d = geom
    q, k, v = _make(h=h, hkv=hkv, d=d)
    gf = _grads(lambda *a: flash_attention(
        *a, causal=causal, block_q=64, block_k=128, interpret=True), q, k, v)
    gr = _grads(lambda *a: _xla_attention(
        *a, causal=causal, mask=None, scale=None), q, k, v)
    for name, a, b in zip("qkv", gf, gr):
        scale = float(jnp.abs(b).max()) + 1e-9
        np.testing.assert_allclose(np.asarray(a) / scale,
                                   np.asarray(b) / scale,
                                   atol=1e-4, err_msg=f"d{name}")


def test_flash_rectangular_and_blocks():
    """Sq != Sk (cross/extended attention) and non-default block sizes."""
    q, k, v = _make(sq=128, sk=512)
    ref = _xla_attention(q, k, v, causal=False, mask=None, scale=None)
    out = flash_attention(q, k, v, causal=False, block_q=64, block_k=128,
                          interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_flash_rectangular_causal_end_aligned():
    """Causal with sq != sk is end-aligned (query i sees keys <= i + sk-sq),
    matching the XLA path's tril(k=sk-sq) — the chunked-decode case."""
    q, k, v = _make(sq=128, sk=512)
    ref = _xla_attention(q, k, v, causal=True, mask=None, scale=None)
    out = flash_attention(q, k, v, causal=True, block_q=64, block_k=128,
                          interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)

    def loss_f(q):
        return jnp.sum(flash_attention(q, k, v, causal=True, block_q=64,
                                       block_k=128, interpret=True) ** 2)

    def loss_r(q):
        return jnp.sum(_xla_attention(q, k, v, causal=True, mask=None,
                                      scale=None) ** 2)

    gf, gr = jax.grad(loss_f)(q), jax.grad(loss_r)(q)
    np.testing.assert_allclose(np.asarray(gf), np.asarray(gr), atol=1e-3)


@pytest.mark.parametrize("geom", [(4, 4, 64), (12, 12, 64), (4, 2, 64),
                                  (4, 2, 128)], ids=_ids)
def test_flash_bf16(geom):
    """Inside the on-chip self-test's tolerances (forward 3e-2, gradients
    ten times that) through the interpreter too."""
    h, hkv, d = geom
    q, k, v = _make(h=h, hkv=hkv, d=d, dtype=jnp.bfloat16)
    flash = lambda *a: flash_attention(*a, causal=True, interpret=True)
    xla = lambda *a: _xla_attention(*a, causal=True, mask=None, scale=None)
    out = flash(q, k, v)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(xla(q, k, v), np.float32),
                               atol=3e-2)
    for a, b in zip(_grads(flash, q, k, v), _grads(xla, q, k, v)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32), atol=3e-1)


def test_flash_custom_scale():
    q, k, v = _make()
    ref = _xla_attention(q, k, v, causal=True, mask=None, scale=0.5)
    out = flash_attention(q, k, v, causal=True, scale=0.5, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-4)


def test_flash_rejects_mask():
    q, k, v = _make(sq=128, sk=128)
    with pytest.raises(NotImplementedError):
        flash_attention(q, k, v, mask=jnp.ones((1, 1, 128, 128), bool),
                        interpret=True)


def test_flash_usable_gate():
    q, k, v = _make(sq=256, sk=256)
    # CPU platform: not usable (auto path keeps XLA)
    assert not flash_attention_usable(q, k, v, True, None)
    # mask always falls back
    assert not flash_attention_usable(q, k, v, True, jnp.ones((1,), bool))
    # indivisible sequence falls back
    q2, k2, v2 = _make(sq=250, sk=250)
    assert not flash_attention_usable(q2, k2, v2, True, None)


def test_dot_product_attention_pallas_switch():
    """implementation='pallas' must run the kernel (interpret off-TPU is the
    kernel path, not a silent XLA fallback)."""
    q, k, v = _make(sq=128, sk=128)
    ref = _xla_attention(q, k, v, causal=True, mask=None, scale=None)
    out = dot_product_attention(q, k, v, causal=True, implementation="pallas")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


@pytest.mark.parametrize("geom", [(4, 2, 64), (8, 4, 64), (4, 4, 32)],
                         ids=_ids)
def test_flash_partitioned_over_mesh_matches_whole(geom):
    """A compiled Mosaic kernel cannot be split by GSPMD, so on a
    multi-device mesh the entry runs it per shard (batch over the
    data-parallel axes, heads over 'model').  Same values and gradients
    as one whole call — checked here with the interpreter kernels on the
    8-device CPU mesh, since only the chip compiles them."""
    from deepspeed_tpu.ops.flash_attention import (mesh_partition,
                                                   run_partitioned)
    from deepspeed_tpu.parallel import groups

    h, hkv, d = geom
    q, k, v = _make(b=4, h=h, hkv=hkv, d=d)
    assert mesh_partition(4, 4, 2) is None          # no topology yet
    groups.initialize_mesh(model_parallel_size=2)   # data=4 x model=2
    part = mesh_partition(4, h, hkv)
    assert part[1:] == (("dout", "data", "expert"), ("model",), 2)
    # a batch or head count that does not divide stays whole
    assert mesh_partition(3, 4, 2)[1] is None
    assert mesh_partition(4, 3, 3)[2:] == (None, 1)
    assert mesh_partition(4, 4, 2, heads_ok=lambda n: False)[2:] == (None, 1)

    def whole(q, k, v, _n=1):
        return flash_attention(q, k, v, causal=True, interpret=True)

    def loss(fn):
        return lambda q, k, v: jnp.sum(fn(q, k, v) ** 2)

    split = jax.jit(lambda q, k, v: run_partitioned(whole, q, k, v, part))
    np.testing.assert_allclose(np.asarray(split(q, k, v)),
                               np.asarray(whole(q, k, v)), atol=2e-5)
    gs = jax.grad(loss(split), argnums=(0, 1, 2))(q, k, v)
    gw = jax.grad(loss(whole), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gs, gw):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)

    # inside a manual region the caller already holds local shards
    from jax.sharding import PartitionSpec as P

    seen = []
    jax.shard_map(lambda x: (seen.append(mesh_partition(4, 4, 2)), x)[1],
                  mesh=part[0], in_specs=P(), out_specs=P())(jnp.zeros(8))
    assert seen == [None]


def test_op_builder_flash_entry():
    from deepspeed_tpu.ops.op_builder import get_op_builder

    fn = get_op_builder("flash_attn").load()
    assert fn is flash_attention


def test_flash_sliding_window_matches_banded_xla():
    """Window as a kernel argument == XLA banded-mask attention, fwd+bwd."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from deepspeed_tpu.ops.attention import _xla_attention
    from deepspeed_tpu.ops.flash_attention import flash_attention

    b, s, h, d = 2, 256, 4, 32
    window = 64
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (b, s, h, d), jnp.float32)
    k = jax.random.normal(ks[1], (b, s, h, d), jnp.float32)
    v = jax.random.normal(ks[2], (b, s, h, d), jnp.float32)

    def f_kernel(q, k, v):
        return flash_attention(q, k, v, causal=True, window=window,
                               block_q=64, block_k=64).sum()

    def f_ref(q, k, v):
        return _xla_attention(q, k, v, causal=True, mask=None, scale=None,
                              window=window).sum()

    out_k = flash_attention(q, k, v, causal=True, window=window,
                            block_q=64, block_k=64)
    out_r = _xla_attention(q, k, v, causal=True, mask=None, scale=None,
                           window=window)
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r),
                               rtol=2e-5, atol=2e-5)
    gk = jax.grad(f_kernel, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    for a, bb in zip(gk, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(bb),
                                   rtol=2e-4, atol=2e-4)


def test_flash_window_requires_causal():
    import jax
    import jax.numpy as jnp
    import pytest

    from deepspeed_tpu.ops.flash_attention import flash_attention

    q = jnp.zeros((1, 128, 2, 32))
    with pytest.raises(ValueError, match="causal"):
        flash_attention(q, q, q, causal=False, window=16)


# ===================================================================== #
# A causal tile's live part (PR 49): the kernels walk the sub-blocks of a
# tile that the band leaves alive, and mask only those an edge crosses
# ===================================================================== #
from deepspeed_tpu.ops import flash_attention as fa  # noqa: E402

#: sq, sk, h, hkv, d, window, (block_q, block_k) or None for the defaults:
#: the shapes where the live range bites
LIVE_PART_CASES = {
    "s1k_d64": (1024, 1024, 1, 1, 64, None, None),
    "s1k_d128": (1024, 1024, 1, 1, 128, None, None),
    "end_aligned_512_of_1024": (512, 1024, 1, 1, 64, None, None),
    "end_aligned_off_the_sub_block": (384, 1024, 1, 1, 64, None, None),
    "window_under_a_sub_block": (1024, 1024, 1, 1, 64, 100, None),
    "window_of_s": (1024, 1024, 1, 1, 64, 1024, None),
    "window_across_two_key_tiles": (1024, 1024, 1, 1, 64, 700, (512, 512)),
    "window_under_a_tile_of_128": (512, 512, 2, 2, 64, 200, (128, 128)),
    "gqa_4_to_1": (1024, 1024, 4, 1, 64, None, None),
    "two_key_tiles_s2k": (2048, 2048, 1, 1, 64, None, None),
    "more_displacements_than_bodies": (1024, 1024, 1, 1, 64, None,
                                       (128, 1024)),
}


@pytest.mark.parametrize("case", LIVE_PART_CASES)
def test_live_part_forward_and_grads_match_xla(case):
    sq, sk, h, hkv, d, window, blocks = LIVE_PART_CASES[case]
    bq, bk = blocks or (None, None)
    q, k, v = _make(b=1, sq=sq, sk=sk, h=h, hkv=hkv, d=d, seed=49)

    def flash(q, k, v):
        return flash_attention(q, k, v, causal=True, window=window,
                               block_q=bq, block_k=bk, interpret=True)

    def ref(q, k, v):
        return _xla_attention(q, k, v, causal=True, mask=None, scale=None,
                              window=window)

    np.testing.assert_allclose(np.asarray(flash(q, k, v)),
                               np.asarray(ref(q, k, v)), atol=2e-5)
    loss = lambda fn: lambda *a: jnp.sum(fn(*a) ** 2)
    gf = jax.grad(loss(flash), argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss(ref), argnums=(0, 1, 2))(q, k, v)
    for name, a, b in zip("qkv", gf, gr):
        scale = float(jnp.abs(b).max()) + 1e-9
        np.testing.assert_allclose(np.asarray(a) / scale,
                                   np.asarray(b) / scale,
                                   atol=1e-4, err_msg=f"d{name}")


def _classes(rows, cols, offset, window):
    """(any visible, all visible) of the rectangle rows x cols (ranges)."""
    u = np.asarray(rows)[:, None] + offset - np.asarray(cols)[None, :]
    keep = (u >= 0) & (u < (window or 1 << 30))
    return keep.any(), keep.all()


def _taken(steps, sub):
    """{sub-block: masked} of a group's products, each sub-block once."""
    seen = {}
    for first, width, masked in steps:
        assert width > 0 and first % sub == 0 and width % sub == 0
        for j in range(first // sub, (first + width) // sub):
            assert j not in seen
            seen[j] = masked
    return seen


@pytest.mark.parametrize("window", [None, 1, 37, 128, 300, 4096])
@pytest.mark.parametrize("offset", [0, 96, 512])
def test_a_group_takes_the_live_sub_blocks_and_masks_the_crossed(offset,
                                                                 window):
    """``_key_steps`` / ``_row_steps`` against the mask itself, a
    sub-block at a time: taken where any element is visible, bare where
    every one is; at sub-blocks of two sizes and tiles off the origin."""
    for sub, n, group in ((128, 4, 128), (64, 8, 192)):
        for first in (0, 128, 384, 640, 1024):
            for tile0 in (0, 512, 1024):
                u0 = first + offset - tile0
                keys = _taken(fa._key_steps(u0, 0, group, n * sub, sub,
                                            window), sub)
                rows = _taken(fa._row_steps(-u0, 0, group, n * sub, sub,
                                            window), sub)
                for j in range(n):
                    span = range(tile0 + j * sub, tile0 + (j + 1) * sub)
                    mine = range(first, first + group)
                    some, every = _classes(mine, span, offset, window)
                    assert (j in keys) == some, (first, tile0, j)
                    assert not some or keys[j] == (not every)
                    # the mirror: ``mine`` as keys under the rows ``span``
                    some, every = _classes(span, mine, -offset, window)
                    assert (j in rows) == some, (first, tile0, j)
                    assert not some or rows[j] == (not every)


@pytest.mark.parametrize("grid,want", [
    ((2, 1, 512, 1024, 0, None), (0, 512)),              # GPT-2 at 8 x 1024
    ((1, 1, 1024, 1024, 0, None), (0,)),                 # ... a q-tile of 1024
    ((4, 4, 1024, 1024, 0, 4096), (0,)),                 # Mistral at 4096
    ((8, 4, 512, 1024, 0, 1024), (0, 512, 1024, 1536)),  # 4 + 4 + 3 + 3 pairs
    ((1, 1, 512, 1024, 512, None), (512,)),              # 512 rows of 1024
    ((2, 2, 128, 128, 0, None), (0,)),
], ids=["s1k_512", "s1k", "s4k_window_of_s", "s4k_window_1k", "end_aligned",
        "128"])
def test_the_crossed_displacements_of_a_grid(grid, want):
    assert fa._tile_bodies(*grid) == want


@pytest.mark.parametrize("window", [None, 300, 1500])
@pytest.mark.parametrize("offset", [0, 512, 1024])
def test_a_step_outside_the_band_names_a_tile_inside_it(offset, window):
    """The index maps hold the k-tile (forward, dQ) or the q-tile (dK/dV)
    of a grid step that does no work at the nearest one that does: the
    pipeline moves nothing for it."""
    bq, bk, nq, nk = 256, 512, 8, 4 + offset // 512
    band = dict(causal=True, block_q=bq, block_k=bk, causal_offset=offset,
                window=window)
    live = np.array([[_classes(range(iq * bq, (iq + 1) * bq),
                               range(ik * bk, (ik + 1) * bk), offset,
                               window)[0]
                      for ik in range(nk)] for iq in range(nq)])
    for iq in range(nq):
        inside = np.flatnonzero(live[iq])
        for ik in range(nk):
            held = int(fa._held_k_tile(iq, ik, nk=nk, **band))
            assert held == np.clip(ik, inside[0], inside[-1]), (iq, ik)
    for ik in range(nk):
        inside = np.flatnonzero(live[:, ik])
        for iq in range(nq):
            held = int(fa._held_q_tile(ik, iq, nq=nq, **band))
            if len(inside):
                assert held == np.clip(iq, inside[0], inside[-1]), (ik, iq)


def test_causal_work_counts_what_the_kernels_execute():
    run, live = fa.causal_work(1024, 1024)
    assert live == 1024 * 1025 // 2 and run / live <= 1.50
    run, live = fa.causal_work(4096, 4096, window=4096)
    assert live == 4096 * 4097 // 2 and run / live <= 1.13
    assert fa.causal_work(1024, 2048, causal=False) == (1024 * 2048,) * 2
    # the mask's own count at an end-aligned shape under a window
    run, live = fa.causal_work(384, 1024, window=200)
    u = np.arange(384)[:, None] + 640 - np.arange(1024)[None, :]
    assert live == int(((u >= 0) & (u < 200)).sum())
    assert live <= run <= 384 * 1024
    # dK/dV takes rows under a group of keys: the same sub-blocks
    sub_q, sub_k = fa._sub_blocks(512, 1024)
    by_rows = sum(sub_k * height
                  for q0 in range(0, 4096, 512)
                  for k0 in range(0, 4096, 1024)
                  for c in range(0, 1024, sub_k)
                  for _, height, _ in fa._row_steps(
                      q0 - k0, c, sub_k, 512, sub_q, 4096))
    assert by_rows == fa.causal_work(4096, 4096, window=4096,
                                     block_q=512)[0]
    # more crossed displacements than bodies: the rest run whole
    run, live = fa.causal_work(1024, 1024, block_q=128, block_k=1024)
    assert len(fa._tile_bodies(8, 1, 128, 1024, 0, None)) == 8 \
        > fa.MAX_TILE_BODIES == 6
    assert run == 128 * (2 * 256 + 2 * 512 + 2 * 768 + 2 * 1024)


@pytest.mark.parametrize("geom", GEOMS[1:2] + GEOMS[3:], ids=_ids)
def test_flash_attention_records_its_work_once_a_trace(geom):
    """``causal_work`` x batch x QUERY heads at every geometry (a KV head
    is multiplied once a query head of its group), and its ``run`` against
    the mask itself: the sub-blocks that hold a visible pair."""
    from deepspeed_tpu.observability.registry import MetricsRegistry

    h, hkv, d = geom
    reg = MetricsRegistry.default()
    before = reg.snapshot()
    q, k, v = _make(b=2, sq=256, sk=256, h=h, hkv=hkv, d=d)
    f = jax.jit(lambda q, k, v: flash_attention(
        q, k, v, causal=True, block_q=128, block_k=128, interpret=True))
    f(q, k, v)
    f(q, k, v)                                  # the traced program again
    after = reg.snapshot()
    run, live = fa.causal_work(256, 256, block_q=128, block_k=128)
    sub_q, sub_k = fa._sub_blocks(128, 128)
    assert run == sub_q * sub_k * sum(
        _classes(range(r, r + sub_q), range(c, c + sub_k), 0, None)[0]
        for r in range(0, 256, sub_q) for c in range(0, 256, sub_k))
    assert live == 256 * 257 // 2 < run < 256 * 256
    assert after["flash/score_elems_run"] \
        - before["flash/score_elems_run"] == 2 * h * run
    assert after["flash/score_elems_live"] \
        - before["flash/score_elems_live"] == 2 * h * live
    assert not reg.unknown_names, reg.unknown_names


@pytest.mark.parametrize("b,s,h,hkv,d,window", [
    (8, 1024, 20, 20, 64, None), (1, 4096, 16, 4, 128, 4096),
    (2, 512, 8, 4, 64, None), (2, 2048, 6, 2, 64, 256),
    (2, 512, 4, 4, 32, None)],
    ids=["gpt2large_d64_s1k", "mistral7b_d128_s4k", "gqa_8_4_d64",
         "gqa_6_2_d64_s2k_window", "d32"])
def test_the_training_cells_shapes_lower_for_the_tpu(monkeypatch, b, s, h,
                                                     hkv, d, window):
    """The three kernels the one route picks at the two training cells'
    shapes (and at GQA groups and 32-lane heads), lowered for the TPU
    from here (no chip): heads under a lane tile take the folded family,
    128-lane heads the [B, H, S, D] kernels whose walk's loops, dynamic
    slices and lane-wise statistics pass the Mosaic lowering; each under
    the kernels' accepted names."""
    monkeypatch.setattr(fa, "on_tpu", lambda: True)
    q = jax.ShapeDtypeStruct((b, s, h, d), jnp.bfloat16)
    k = jax.ShapeDtypeStruct((b, s, hkv, d), jnp.bfloat16)

    def loss(q, k, v):
        return jnp.sum(dot_product_attention(
            q, k, v, causal=True, window=window).astype(jnp.float32) ** 2)

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).trace(q, k, k).lower(
        lowering_platforms=("tpu",)).as_text()
    assert text.count("tpu_custom_call") == 3
    family = "_folded" if fa.folded_heads_per_block(h, hkv, d) else ""
    fwd = "_onepass" if s <= 1024 else ""
    for name in (f"_fwd_kernel{family}{fwd}", f"_bwd_dq_kernel{family}",
                 f"_bwd_dkv_kernel{family}"):
        assert f'kernel_name = "{name}"' in text, name


# ===================================================================== #
# Folded ([B, S, H*D]) layout-native kernels
# ===================================================================== #
from deepspeed_tpu.ops.flash_attention import (  # noqa: E402
    flash_attention_folded, folded_heads_per_block)


def _make_folded(b=2, sq=256, sk=256, h=4, hkv=4, d=64, dtype=jnp.float32,
                 seed=0):
    """Returns folded (q, k, v) plus their [B,S,H,D] views for the ref."""
    q, k, v = _make(b=b, sq=sq, sk=sk, h=h, hkv=hkv, d=d, dtype=dtype,
                    seed=seed)
    fold = lambda t: t.reshape(t.shape[0], t.shape[1], -1)
    return (fold(q), fold(k), fold(v)), (q, k, v)


# Heads under a lane tile, in groups of whole tiles: pairs at 64 lanes
# (GPT-2's 12 heads among them), a GQA group of 2 that widens the pair
# to four heads, four 32-lane heads a tile (the geometries the paired
# family's tests held until PR 54; its GQA group of 3, six heads a
# group, is past what the chip's VMEM holds and is a case of GEOMS
# alone); the explicit small blocks force the multi-k-block
# online-softmax kernel where the defaults would select the one-pass
# variant.
FOLDED_GEOMS = [(4, 4, 64), (4, 2, 64), (12, 12, 64), (8, 4, 64),
                (4, 4, 32)]


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("h,hkv,d", FOLDED_GEOMS)
def test_folded_forward_matches_xla(h, hkv, d, causal):
    (qf, kf, vf), (q, k, v) = _make_folded(h=h, hkv=hkv, d=d)
    ref = _xla_attention(q, k, v, causal=causal, mask=None, scale=None)
    for blocks in ({}, {"block_q": 64, "block_k": 128}):
        out = flash_attention_folded(qf, kf, vf, num_heads=h,
                                     num_kv_heads=hkv, causal=causal,
                                     interpret=True, **blocks)
        np.testing.assert_allclose(
            np.asarray(out).reshape(ref.shape), np.asarray(ref), atol=2e-5)


@pytest.mark.parametrize("h,hkv,d", FOLDED_GEOMS)
def test_folded_grads_match_xla(h, hkv, d):
    """jax.grad through flash_attention_folded exercises the custom_vjp
    backward (folded dq + folded group-summed dk/dv)."""
    (qf, kf, vf), (q, k, v) = _make_folded(h=h, hkv=hkv, d=d)

    def loss_f(q_, k_, v_):
        return jnp.sum(flash_attention_folded(
            q_, k_, v_, num_heads=h, num_kv_heads=hkv, causal=True,
            block_q=64, block_k=128, interpret=True) ** 2)

    def loss_r(q_, k_, v_):
        return jnp.sum(_xla_attention(q_, k_, v_, causal=True, mask=None,
                                      scale=None) ** 2)

    gf = jax.grad(loss_f, argnums=(0, 1, 2))(qf, kf, vf)
    gr = jax.grad(loss_r, argnums=(0, 1, 2))(q, k, v)
    for name, a, b in zip("qkv", gf, gr):
        scale = float(jnp.abs(b).max()) + 1e-9
        np.testing.assert_allclose(np.asarray(a).reshape(b.shape) / scale,
                                   np.asarray(b) / scale,
                                   atol=1e-4, err_msg=f"d{name}")


@pytest.mark.parametrize("h,hkv,d", [(4, 4, 64), (12, 12, 64), (4, 2, 64)])
def test_folded_bf16_within_selftest_tolerances(h, hkv, d):
    """The acceptance tolerances of the on-chip selftest (fwd 2e-2, grad
    2.5e-1 at bf16) hold through the interpreter too."""
    (qf, kf, vf), (q, k, v) = _make_folded(h=h, hkv=hkv, d=d,
                                           dtype=jnp.bfloat16)
    ref = _xla_attention(q, k, v, causal=True, mask=None, scale=None)
    out = flash_attention_folded(qf, kf, vf, num_heads=h, num_kv_heads=hkv,
                                 causal=True, interpret=True)
    assert out.dtype == jnp.bfloat16
    assert float(jnp.max(jnp.abs(
        out.astype(jnp.float32).reshape(ref.shape)
        - ref.astype(jnp.float32)))) < 2e-2

    gf = jax.grad(lambda a, b, c: jnp.sum(flash_attention_folded(
        a, b, c, num_heads=h, num_kv_heads=hkv, causal=True,
        interpret=True).astype(jnp.float32) ** 2),
        argnums=(0, 1, 2))(qf, kf, vf)
    gr = jax.grad(lambda a, b, c: jnp.sum(_xla_attention(
        a, b, c, causal=True, mask=None,
        scale=None).astype(jnp.float32) ** 2),
        argnums=(0, 1, 2))(q, k, v)
    err = max(float(jnp.max(jnp.abs(
        a.astype(jnp.float32).reshape(b.shape) - b.astype(jnp.float32))))
        for a, b in zip(gf, gr))
    assert err < 2.5e-1


def test_folded_sliding_window_matches_banded_xla():
    """Window fwd AND bwd (the window term of the run predicate / keep
    mask must hold through the custom_vjp, not just the forward)."""
    (qf, kf, vf), (q, k, v) = _make_folded(h=4, hkv=4, d=64)
    ref = _xla_attention(q, k, v, causal=True, mask=None, scale=None,
                         window=64)
    out = flash_attention_folded(qf, kf, vf, num_heads=4, causal=True,
                                 window=64, block_q=64, block_k=64,
                                 interpret=True)
    np.testing.assert_allclose(np.asarray(out).reshape(ref.shape),
                               np.asarray(ref), rtol=2e-5, atol=2e-5)

    gf = jax.grad(lambda a, b, c: jnp.sum(flash_attention_folded(
        a, b, c, num_heads=4, causal=True, window=64, block_q=64,
        block_k=64, interpret=True) ** 2), argnums=(0, 1, 2))(qf, kf, vf)
    gr = jax.grad(lambda a, b, c: jnp.sum(_xla_attention(
        a, b, c, causal=True, mask=None, scale=None,
        window=64) ** 2), argnums=(0, 1, 2))(q, k, v)
    for name, a, b in zip("qkv", gf, gr):
        np.testing.assert_allclose(np.asarray(a).reshape(b.shape),
                                   np.asarray(b), rtol=2e-4, atol=2e-4,
                                   err_msg=f"d{name}")


def test_folded_rectangular_causal_end_aligned():
    (qf, kf, vf), (q, k, v) = _make_folded(sq=128, sk=512)
    ref = _xla_attention(q, k, v, causal=True, mask=None, scale=None)
    out = flash_attention_folded(qf, kf, vf, num_heads=4, causal=True,
                                 block_q=64, block_k=128, interpret=True)
    np.testing.assert_allclose(np.asarray(out).reshape(ref.shape),
                               np.asarray(ref), atol=2e-5)


def test_folded_heads_per_block_grouping():
    assert folded_heads_per_block(12, 12, 64) == 2   # MHA d64: lane pair
    assert folded_heads_per_block(4, 2, 64) == 4     # GQA g=2 d64
    assert folded_heads_per_block(4, 4, 32) == 4     # four heads a tile
    assert folded_heads_per_block(8, 2, 128) is None  # d128: the other family
    # past the chip's VMEM at the default tiles: six or eight heads a
    # group, or more than 256 lanes (four heads of 96)
    assert folded_heads_per_block(6, 2, 64) is None
    assert folded_heads_per_block(8, 2, 64) is None
    assert folded_heads_per_block(8, 8, 16) is None
    assert folded_heads_per_block(16, 16, 96) is None
    assert folded_heads_per_block(3, 3, 64) is None  # 3 heads: no pair
    assert folded_heads_per_block(4, 4, 48) is None  # 48 lanes: no tile


def test_folded_validation_errors():
    q = jnp.zeros((1, 128, 256))
    with pytest.raises(ValueError, match="divisible"):
        flash_attention_folded(q, q, q, num_heads=3, interpret=True)
    with pytest.raises(ValueError, match="lane-aligned"):
        flash_attention_folded(jnp.zeros((1, 128, 192)),
                               jnp.zeros((1, 128, 192)),
                               jnp.zeros((1, 128, 192)),
                               num_heads=3, interpret=True)
    with pytest.raises(NotImplementedError):
        flash_attention_folded(q, q, q, num_heads=4,
                               mask=jnp.ones((1,), bool), interpret=True)
    with pytest.raises(ValueError, match="rank-3"):
        flash_attention_folded(jnp.zeros((1, 128, 4, 64)),
                               jnp.zeros((1, 128, 4, 64)),
                               jnp.zeros((1, 128, 4, 64)),
                               num_heads=4, interpret=True)


# ===================================================================== #
# The one route (PR 54): the models hand ``dot_product_attention`` their
# [B, S, H, D] heads and the gate picks the flash kernels or XLA
# ===================================================================== #
@pytest.fixture
def chip_route(monkeypatch):
    """``flash_attention_usable`` as a TPU answers it (its shape rules as
    they are, ``on_tpu()`` true inside it alone, so the kernels still run
    in interpret mode), and the two families' entries spied on; returns
    the list of what each call took: False, "folded" or "bshd"."""
    usable, took = fa.flash_attention_usable, []

    def gate(*args):
        with monkeypatch.context() as m:
            m.setattr(fa, "on_tpu", lambda: True)
            ok = usable(*args)
        if not ok:
            took.append(False)
        return ok

    def spy(name, fn):
        def entry(*args, **kw):
            took.append(name)
            return fn(*args, **kw)
        return entry

    monkeypatch.setattr(fa, "flash_attention_usable", gate)
    monkeypatch.setattr(fa, "flash_attention_folded",
                        spy("folded", fa.flash_attention_folded))
    monkeypatch.setattr(fa, "flash_attention",
                        spy("bshd", fa.flash_attention))
    return took


@pytest.mark.parametrize("geom,want", [
    ((12, 12, 64), "folded"), ((4, 2, 64), "folded"), ((4, 4, 32), "folded"),
    ((3, 3, 64), "bshd"),               # three heads make no whole tile
    ((6, 2, 64), "bshd"),               # six heads a group: past the VMEM
    ((4, 4, 128), "bshd"), ((4, 2, 128), "bshd")], ids=_ids)
def test_the_head_size_picks_the_family(chip_route, geom, want):
    """Heads under a 128-lane tile that group to whole tiles go to the
    folded kernels (the chip's A/B of PR 54), everything else the gate
    admits to the [B, H, S, D] kernels; same values either way."""
    h, hkv, d = geom
    q, k, v = _make(h=h, hkv=hkv, d=d)
    out = dot_product_attention(q, k, v, causal=True)
    assert chip_route == [want]
    ref = _xla_attention(q, k, v, causal=True, mask=None, scale=None)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


@pytest.mark.parametrize("why,shape,mask", [
    ("head_size_no_multiple_of_8", dict(d=20), False),
    ("rows_no_whole_block", dict(sq=250, sk=250), False),
    ("under_a_block_of_128", dict(sq=64, sk=64), False),
    ("a_mask_of_the_callers", dict(), True),
])
def test_a_geometry_the_gate_refuses_takes_the_xla_route(chip_route, why,
                                                         shape, mask):
    q, k, v = _make(**shape)
    mask = jnp.tril(jnp.ones(q.shape[1:2] * 2, bool))[None, None] \
        if mask else None
    out = dot_product_attention(q, k, v, causal=True, mask=mask)
    assert chip_route == [False]
    ref = _xla_attention(q, k, v, causal=True, mask=mask, scale=None)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


@pytest.mark.parametrize("model_name,want", [("gpt2", "folded"),
                                             ("llama", "bshd")])
def test_a_models_training_forward_takes_the_one_route(chip_route,
                                                       model_name, want):
    """GPT-2 at 4 heads of 64 and Llama at GQA 4-on-2 heads of 128, 128
    tokens: the loss and its gradients through the family the head size
    picks against the XLA composition; neither config has a field that
    picks the route."""
    if model_name == "gpt2":
        from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2LMHeadModel
        model = GPT2LMHeadModel(GPT2Config.tiny(
            dtype=jnp.float32, hidden_size=256))
    else:
        from deepspeed_tpu.models.llama import LlamaConfig, LlamaForCausalLM
        model = LlamaForCausalLM(LlamaConfig.tiny(
            dtype=jnp.float32, hidden_size=512))
    ids = (np.arange(2 * 128, dtype=np.int32).reshape(2, 128) * 7) % 250
    params = model.init(jax.random.key(0), ids)
    chip_route.clear()
    loss = lambda p: model.apply(p, ids, labels=ids)
    got, grads = jax.value_and_grad(loss)(params)
    assert chip_route == [want] * model.config.num_hidden_layers
    with pytest.MonkeyPatch.context() as m:     # the XLA route
        m.setattr(fa, "flash_attention_usable", lambda *a: False)
        want_loss, want_grads = jax.value_and_grad(loss)(params)
    np.testing.assert_allclose(float(got), float(want_loss), rtol=1e-5)
    for a, b in zip(jax.tree.leaves(grads), jax.tree.leaves(want_grads)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-4)


def test_flash_steady_state_recompile_and_sync_free(trace_guard):
    """A warmed jitted train-style step over the kernels (forward and the
    custom_vjp's two backward kernels) builds no new executable and makes
    no host sync when it is called again."""
    q, k, v = _make(h=4, hkv=2, d=64)

    @jax.jit
    def step(q, k, v):
        return jax.value_and_grad(lambda *a: jnp.sum(flash_attention(
            *a, causal=True, block_q=64, block_k=128, interpret=True) ** 2),
            argnums=(0, 1, 2))(q, k, v)

    step(q, k, v)[0].block_until_ready()
    with trace_guard(max_compiles=0, max_host_syncs=0):
        for _ in range(3):
            out = step(q, k, v)
    jax.block_until_ready(out)


def test_a_config_that_still_names_attention_layout_parses():
    """The key PR 54 took out is to the config what any key it does not
    know is: kept in the dict it was given, read by nothing, refused for no
    value."""
    from deepspeed_tpu.runtime.config import DeepSpeedConfig

    base = {"train_micro_batch_size_per_gpu": 1}
    for extra in ({"attention_layout": "folded"}, {"attention_layout": 7},
                  {"a_key_nobody_knows": "x"}):
        cfg = DeepSpeedConfig({**base, **extra})
        assert vars(cfg).keys() == vars(DeepSpeedConfig(base)).keys()
        assert cfg._param_dict == {**base, **extra}
