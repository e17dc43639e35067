"""PR 30, chip call 6 (ran as ``build/pr30/bias_probe.py``): is the decode
walk's arithmetic on the chip the dense read's?

1. float32 -> bf16 inside a Mosaic kernel against XLA's, 256k values;
2. ``exp`` inside a Mosaic kernel against XLA's and against float64;
3. the walk and the dense read against an exact float32 attention (XLA at
   ``highest`` precision) at the three serving cells' head layouts: RMS
   error, the error's component along the exact result (a bias in the
   attention weights shows there: V carries a mean), its mean.

    python3 tools/chip_calls/pr30_bias_probe.py
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

import jax                                              # noqa: E402
import jax.numpy as jnp                                 # noqa: E402
import numpy as np                                      # noqa: E402
from jax.experimental import pallas as pl               # noqa: E402

from deepspeed_tpu.inference.v2.kernels import (        # noqa: E402
    paged_decode_attention)
from deepspeed_tpu.inference.v2.model_implementations.ragged_llama import (  # noqa: E402
    _dense_pool_read)


def _cast(x_ref, o_ref):
    o_ref[...] = x_ref[...].astype(jnp.bfloat16)


def _exp(x_ref, o_ref):
    o_ref[...] = jnp.exp(x_ref[...])


x = jax.random.uniform(jax.random.key(0), (256, 1024), jnp.float32, 0.0, 1.0)
got = pl.pallas_call(_cast, out_shape=jax.ShapeDtypeStruct(
    x.shape, jnp.bfloat16))(x)
want = x.astype(jnp.bfloat16)
print("mosaic f32->bf16: equal to XLA's", bool(jnp.all(got == want)),
      "mean rel err mosaic", float(((got.astype(jnp.float32) - x) / x).mean()),
      "xla", float(((want.astype(jnp.float32) - x) / x).mean()))

xe = -jax.random.uniform(jax.random.key(1), (256, 1024), jnp.float32,
                         0.0, 20.0)
ge = pl.pallas_call(_exp, out_shape=jax.ShapeDtypeStruct(
    xe.shape, jnp.float32))(xe)
ex = np.exp(np.asarray(xe, np.float64))
for name, val in (("mosaic", ge), ("xla", jnp.exp(xe))):
    rel = (np.asarray(val, np.float64) - ex) / ex
    print(f"exp rel err {name}: max {np.abs(rel).max()} mean {rel.mean()}")

BS = 128
for nb, hkv, g, d in ((192, 16, 1, 128), (160, 8, 4, 128), (512, 2, 8, 256)):
    S, B = 32, 8
    ks = jax.random.split(jax.random.key(3), 3)
    kp = jax.random.normal(ks[0], (nb * BS, hkv, d),
                           jnp.float32).astype(jnp.bfloat16)
    vp = (jax.random.normal(ks[1], (nb * BS, hkv, d), jnp.float32)
          + 0.5).astype(jnp.bfloat16)
    q = (jax.random.normal(ks[2], (S, hkv * g, d), jnp.float32)
         * 0.3).astype(jnp.bfloat16)
    rng = np.random.default_rng(0)
    tables = jnp.pad(jnp.asarray(
        (rng.permutation(nb - 1)[:S * 4] + 1).reshape(S, 4).astype(np.int32)),
        ((0, 0), (0, B - 4)))
    pos = jnp.asarray(rng.integers(300, 4 * BS, size=S).astype(np.int32))
    slot = jnp.arange(S, dtype=jnp.int32)
    batch = {"block_tables": tables, "token_slot": slot, "token_pos": pos}
    with jax.default_matmul_precision("highest"):
        exact = np.asarray(_dense_pool_read(
            q.astype(jnp.float32), kp.astype(jnp.float32),
            vp.astype(jnp.float32), None, None, batch, BS, None), np.float64)
    for name, out in (
            ("dense", _dense_pool_read(q, kp, vp, None, None, batch, BS,
                                       None)),
            ("walk", paged_decode_attention(q, kp, vp, tables, slot, pos,
                                            block_size=BS))):
        e = np.asarray(out.astype(jnp.float32), np.float64) - exact
        print((hkv, g, d), name, "rms", float(np.sqrt((e ** 2).mean())),
              "bias along exact",
              float((e * exact).sum() / (exact ** 2).sum()),
              "mean", float(e.mean()))
