"""Head-paired flash attention (d<128 lane-full tiles) — parity against
the XLA composition, fallback routing, config plumbing, and the jit
steady-state contract.

Runs the real Pallas kernels through the interpreter on CPU, so the
exact TPU kernel code is exercised by the suite (same pattern as
test_flash_attention.py).  Tolerances are the acceptance bar from
ISSUE 15: fwd <= 2e-5 / grad <= 1e-4 at f32.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops.attention import (_xla_attention,
                                         get_default_attention_layout,
                                         paired_attention,
                                         set_default_attention_layout)
from deepspeed_tpu.ops.flash_attention import (flash_attention_paired,
                                               flash_attention_paired_usable,
                                               paired_heads_per_block)


def _make(b=2, sq=256, sk=256, h=4, hkv=4, d=64, dtype=jnp.float32, seed=0):
    kq, kk, kv = jax.random.split(jax.random.key(seed), 3)
    q = jax.random.normal(kq, (b, sq, h, d), dtype)
    k = jax.random.normal(kk, (b, sk, hkv, d), dtype)
    v = jax.random.normal(kv, (b, sk, hkv, d), dtype)
    fold = lambda t: t.reshape(t.shape[0], t.shape[1], -1)
    return (fold(q), fold(k), fold(v)), (q, k, v)


# the honest 12-head/d64 GPT-2 geometry (the pairing's raison d'etre),
# GQA pairs sharing one KV head, an uneven-pair GQA group (g=3: one
# pair straddles a KV boundary and must still be per-head exact), and
# the d=32 quad-pack; explicit small blocks force the multi-k-block
# lane-blocked online-softmax kernel where defaults pick one-pass.
PAIRED_GEOMS = [(12, 12, 64), (4, 2, 64), (8, 4, 64), (6, 2, 64),
                (4, 4, 32)]


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("h,hkv,d", PAIRED_GEOMS)
def test_paired_forward_matches_xla(h, hkv, d, causal):
    (qf, kf, vf), (q, k, v) = _make(h=h, hkv=hkv, d=d)
    ref = _xla_attention(q, k, v, causal=causal, mask=None, scale=None)
    for blocks in ({}, {"block_q": 64, "block_k": 128}):
        out = flash_attention_paired(qf, kf, vf, num_heads=h,
                                     num_kv_heads=hkv, causal=causal,
                                     interpret=True, **blocks)
        np.testing.assert_allclose(
            np.asarray(out).reshape(ref.shape), np.asarray(ref), atol=2e-5)


@pytest.mark.parametrize("h,hkv,d", PAIRED_GEOMS)
def test_paired_grads_match_xla(h, hkv, d):
    """jax.grad through flash_attention_paired exercises the custom_vjp
    backward (lane-masked dq + group-summed dk/dv, all full-lane)."""
    (qf, kf, vf), (q, k, v) = _make(h=h, hkv=hkv, d=d)

    def loss_f(q_, k_, v_):
        return jnp.sum(flash_attention_paired(
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


@pytest.mark.parametrize("h,hkv,d", [(12, 12, 64), (4, 2, 64)])
def test_paired_bf16_within_selftest_tolerances(h, hkv, d):
    """The acceptance tolerances of the on-chip selftest (fwd 3e-2, grad
    3e-1 at bf16) hold through the interpreter too."""
    (qf, kf, vf), (q, k, v) = _make(h=h, hkv=hkv, d=d, dtype=jnp.bfloat16)
    ref = _xla_attention(q, k, v, causal=True, mask=None, scale=None)
    out = flash_attention_paired(qf, kf, vf, num_heads=h, num_kv_heads=hkv,
                                 causal=True, interpret=True)
    assert out.dtype == jnp.bfloat16
    assert float(jnp.max(jnp.abs(
        out.astype(jnp.float32).reshape(ref.shape)
        - ref.astype(jnp.float32)))) < 3e-2

    gf = jax.grad(lambda a, b, c: jnp.sum(flash_attention_paired(
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
    assert err < 3e-1


def test_paired_sliding_window_matches_banded_xla():
    """Window fwd AND bwd — the keep/run predicates must hold per
    sub-head through the lane-masked custom_vjp."""
    (qf, kf, vf), (q, k, v) = _make(h=4, hkv=4, d=64)
    ref = _xla_attention(q, k, v, causal=True, mask=None, scale=None,
                         window=64)
    out = flash_attention_paired(qf, kf, vf, num_heads=4, causal=True,
                                 window=64, block_q=64, block_k=64,
                                 interpret=True)
    np.testing.assert_allclose(np.asarray(out).reshape(ref.shape),
                               np.asarray(ref), rtol=2e-5, atol=2e-5)

    gf = jax.grad(lambda a, b, c: jnp.sum(flash_attention_paired(
        a, b, c, num_heads=4, causal=True, window=64, block_q=64,
        block_k=64, interpret=True) ** 2), argnums=(0, 1, 2))(qf, kf, vf)
    gr = jax.grad(lambda a, b, c: jnp.sum(_xla_attention(
        a, b, c, causal=True, mask=None, scale=None,
        window=64) ** 2), argnums=(0, 1, 2))(q, k, v)
    for name, a, b in zip("qkv", gf, gr):
        np.testing.assert_allclose(np.asarray(a).reshape(b.shape),
                                   np.asarray(b), rtol=2e-4, atol=2e-4,
                                   err_msg=f"d{name}")


def test_paired_rectangular_causal_end_aligned():
    """Sq != Sk end-aligned causal (the chunked-decode case), fwd+bwd."""
    (qf, kf, vf), (q, k, v) = _make(sq=128, sk=512)
    ref = _xla_attention(q, k, v, causal=True, mask=None, scale=None)
    out = flash_attention_paired(qf, kf, vf, num_heads=4, causal=True,
                                 block_q=64, block_k=128, interpret=True)
    np.testing.assert_allclose(np.asarray(out).reshape(ref.shape),
                               np.asarray(ref), atol=2e-5)

    gf = jax.grad(lambda a: jnp.sum(flash_attention_paired(
        a, kf, vf, num_heads=4, causal=True, block_q=64, block_k=128,
        interpret=True) ** 2))(qf)
    gr = jax.grad(lambda a: jnp.sum(_xla_attention(
        a, k, v, causal=True, mask=None, scale=None) ** 2))(q)
    np.testing.assert_allclose(np.asarray(gf).reshape(gr.shape),
                               np.asarray(gr), atol=1e-3)


# ===================================================================== #
# Pairing rule + fallback routing
# ===================================================================== #
def test_paired_heads_per_block_rule():
    assert paired_heads_per_block(12, 12, 64) == 2   # MHA d64: lane pair
    assert paired_heads_per_block(4, 2, 64) == 4     # GQA g=2: pair/KV head
    assert paired_heads_per_block(8, 4, 64) == 4
    assert paired_heads_per_block(4, 4, 32) == 4     # d32: quad-pack
    assert paired_heads_per_block(8, 2, 128) is None  # d>=128: use folded
    assert paired_heads_per_block(3, 3, 64) is None  # odd heads: no pad rule
    assert paired_heads_per_block(4, 4, 48) is None  # 48 !| 128: no tile
    assert paired_heads_per_block(2, 1, 96) is None


def test_paired_validation_errors():
    q = jnp.zeros((1, 128, 4 * 128))
    with pytest.raises(ValueError, match="lane-full"):
        # d=128 is folded's job, the paired entry refuses it loudly
        flash_attention_paired(q, q, q, num_heads=4, interpret=True)
    q3 = jnp.zeros((1, 128, 3 * 64))
    with pytest.raises(ValueError, match="lane-full"):
        flash_attention_paired(q3, q3, q3, num_heads=3, interpret=True)
    with pytest.raises(ValueError, match="rank-3"):
        flash_attention_paired(jnp.zeros((1, 128, 4, 64)),
                               jnp.zeros((1, 128, 4, 64)),
                               jnp.zeros((1, 128, 4, 64)),
                               num_heads=4, interpret=True)
    q2 = jnp.zeros((1, 128, 2 * 64))
    with pytest.raises(NotImplementedError):
        flash_attention_paired(q2, q2, q2, num_heads=2,
                               mask=jnp.ones((1,), bool), interpret=True)


def test_paired_usable_gate():
    (qf, kf, vf), _ = _make()
    # CPU platform: not usable (auto path keeps the fallback)
    assert not flash_attention_paired_usable(qf, kf, vf, 4, 4, True, None)
    # mask always falls back
    assert not flash_attention_paired_usable(qf, kf, vf, 4, 4, True,
                                             jnp.ones((1,), bool))
    # unpairable geometries fall back
    (q3, k3, v3), _ = _make(h=3, hkv=3, d=64)
    assert not flash_attention_paired_usable(q3, k3, v3, 3, 3, True, None)
    (q128, k128, v128), _ = _make(h=2, hkv=2, d=128)
    assert not flash_attention_paired_usable(q128, k128, v128, 2, 2, True,
                                             None)


def test_paired_attention_pallas_switch_and_fallback():
    """implementation='pallas' runs the paired kernel (interpret
    off-TPU); the auto path off-TPU falls back through folded/bshd and
    still matches; ineligible geometries (d=128, odd heads) route to
    the folded path instead of failing."""
    (qf, kf, vf), (q, k, v) = _make(h=4, hkv=2, d=64)
    ref = _xla_attention(q, k, v, causal=True, mask=None, scale=None)
    out_kernel = paired_attention(qf, kf, vf, num_heads=4, num_kv_heads=2,
                                  causal=True, implementation="pallas")
    np.testing.assert_allclose(np.asarray(out_kernel).reshape(ref.shape),
                               np.asarray(ref), atol=2e-5)
    out_auto = paired_attention(qf, kf, vf, num_heads=4, num_kv_heads=2,
                                causal=True)
    np.testing.assert_allclose(np.asarray(out_auto).reshape(ref.shape),
                               np.asarray(ref), atol=2e-5)
    # d=128: pairing inapplicable -> folded path, still exact
    (qf8, kf8, vf8), (q8, k8, v8) = _make(h=2, hkv=2, d=128)
    ref8 = _xla_attention(q8, k8, v8, causal=True, mask=None, scale=None)
    out8 = paired_attention(qf8, kf8, vf8, num_heads=2, causal=True,
                            implementation="pallas")
    np.testing.assert_allclose(np.asarray(out8).reshape(ref8.shape),
                               np.asarray(ref8), atol=2e-5)
    # odd heads: no pad rule -> auto falls through to the bshd path
    (q3f, k3f, v3f), (q3, k3, v3) = _make(h=3, hkv=3, d=64)
    ref3 = _xla_attention(q3, k3, v3, causal=True, mask=None, scale=None)
    out3 = paired_attention(q3f, k3f, v3f, num_heads=3, causal=True)
    np.testing.assert_allclose(np.asarray(out3).reshape(ref3.shape),
                               np.asarray(ref3), atol=2e-5)


# ===================================================================== #
# Config plumbing (attention_layout: "paired")
# ===================================================================== #
@pytest.fixture
def _restore_layout():
    prev = get_default_attention_layout()
    yield
    set_default_attention_layout(prev)


def test_paired_layout_config_parse(_restore_layout):
    from deepspeed_tpu.runtime.config import DeepSpeedConfig

    base = {"train_micro_batch_size_per_gpu": 1}
    cfg = DeepSpeedConfig({**base, "attention_layout": "paired"})
    assert cfg.attention_layout == "paired"
    assert cfg.attention_layout_explicit
    set_default_attention_layout("paired")
    assert get_default_attention_layout() == "paired"


@pytest.mark.parametrize("model_name", ["gpt2", "llama"])
def test_paired_layout_selects_and_falls_back(model_name, _restore_layout):
    """A model with attention_layout='paired' routes through
    paired_attention (off-TPU: the folded/bshd fallback) and must match
    the bshd path exactly; None defers to the process default."""
    if model_name == "gpt2":
        from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2LMHeadModel
        make = lambda layout: GPT2LMHeadModel(
            GPT2Config.tiny(dtype=jnp.float32, attention_layout=layout))
    else:
        from deepspeed_tpu.models.llama import (LlamaConfig,
                                                LlamaForCausalLM)
        make = lambda layout: LlamaForCausalLM(
            LlamaConfig.tiny(dtype=jnp.float32, attention_layout=layout))

    ids = np.arange(32, dtype=np.int32).reshape(1, 32) % 250
    params = make("bshd").init(jax.random.key(0), ids)
    ref = make("bshd").apply(params, ids)
    out_paired = make("paired").apply(params, ids)
    np.testing.assert_allclose(np.asarray(out_paired), np.asarray(ref),
                               atol=1e-5, rtol=1e-5)
    set_default_attention_layout("paired")
    out_default = make(None).apply(params, ids)
    np.testing.assert_allclose(np.asarray(out_default), np.asarray(ref),
                               atol=1e-5, rtol=1e-5)


# ===================================================================== #
# jit steady state: 0 recompiles / 0 host syncs
# ===================================================================== #
def test_paired_steady_state_recompile_and_sync_free(trace_guard):
    """A warmed jitted train-style step over the paired kernel (fwd +
    custom_vjp bwd) builds no new executables and performs no host
    syncs across repeat calls — the TraceGuard contract the
    attention_layout='paired' engine path rides on."""
    (qf, kf, vf), _ = _make(h=4, hkv=2, d=64, sq=256, sk=256)

    @jax.jit
    def step(q_, k_, v_):
        def loss(a, b, c):
            return jnp.sum(flash_attention_paired(
                a, b, c, num_heads=4, num_kv_heads=2, causal=True,
                block_q=64, block_k=128, interpret=True) ** 2)
        l, g = jax.value_and_grad(loss, argnums=(0, 1, 2))(q_, k_, v_)
        return l, g

    # warm: compile once
    step(qf, kf, vf)[0].block_until_ready()
    with trace_guard(max_compiles=0, max_host_syncs=0):
        for _ in range(3):
            out = step(qf, kf, vf)
    jax.block_until_ready(out)
