"""The Gated DeltaNet (linear-attention) mixer of the families that keep a
recurrent matrix per value head and a convolution's tail per sequence in a
state slot (``ragged/state_pool.py``): Qwen3-Next's (16 key heads repeated
to 32 value heads of 128 x 128, write strengths in (0, 1), behind the
block's input norm) and Olmo-Hybrid's (30 = 30 heads of 96 keys x 192
values, write strengths in (0, 2), on the raw residual stream of a
post-norm block).  One function: what differs is read from the config, and
the norm before and the norm after the mixer belong to the caller.

Layout of the projections (what a checkpoint loader has to produce):
``in_proj_qkvz`` columns are ``q | k | v | z`` (all heads of q, then of k,
...), ``in_proj_ba`` columns ``b | a``, ``conv1d/kernel`` is ``[taps,
channels]`` over ``q | k | v`` with the LAST tap on the current token.
The convolution is ``modules/conv.py::_causal_conv`` (the function LFM2's
and Jamba's layers call): the one-token rows read and write their slots'
tails by one-hot matmuls, the tile segment takes the chunk form an entry a
tile, and the slot's ``conv`` leaf is the tail flat in one row, ``[(taps -
1) channels]``.
Device scopes, under the caller's ``layers_<i>``: ``gdn/in_proj`` (both
projections), ``gdn/conv``, ``gdn/rule``, ``gdn/out`` (gated norm and
``out_proj``)."""

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from deepspeed_tpu.inference.v2.modules.conv import _causal_conv, _silu
from deepspeed_tpu.ops.gated_delta_rule import (
    gdn_chunk,
    gdn_step,
    state_leaf_shape,
)
from deepspeed_tpu.ops.quantized_matmul import qmm

F32 = jnp.float32


def gdn_conv_dim(cfg) -> int:
    """Channels of the mixer's convolution: ``q | k | v``."""
    return 2 * cfg.linear_num_key_heads * cfg.linear_key_head_dim \
        + cfg.linear_num_value_heads * cfg.linear_value_head_dim


def gdn_state_leaves(cfg) -> Dict[str, Any]:
    """What one sequence keeps in a slot for ONE such layer
    (``state_spec``'s leaves): the float32 matrices, in the layout the
    rule's kernels keep them in (``ops/gated_delta_rule.py::
    state_leaf_shape``: head pairs side by side on the lanes where one
    head's values fill no whole lane tile and two heads' do), and the
    convolution's last ``taps - 1`` inputs, flat in one row
    (``modules/conv.py``)."""
    return {
        "state": (state_leaf_shape(cfg.linear_num_value_heads,
                                   cfg.linear_key_head_dim,
                                   cfg.linear_value_head_dim), F32),
        "conv": (((cfg.linear_conv_kernel_dim - 1) * gdn_conv_dim(cfg),),
                 cfg.dtype)}


def gdn_param_shapes(cfg, sds) -> Dict[str, Any]:
    """The mixer's parameter tree as shapes (every matrix [in, out])."""
    h, hv, dv = cfg.hidden_size, cfg.linear_num_value_heads, \
        cfg.linear_value_head_dim
    conv_dim = gdn_conv_dim(cfg)
    return {"in_proj_qkvz": {"kernel": sds(h, conv_dim + hv * dv)},
            "in_proj_ba": {"kernel": sds(h, 2 * hv)},
            "conv1d": {"kernel": sds(cfg.linear_conv_kernel_dim, conv_dim)},
            "A_log": sds(hv), "dt_bias": sds(hv),
            "norm": {"scale": sds(dv)},
            "out_proj": {"kernel": sds(hv * dv, h)}}


def gdn_mixer(la, xn, layer_cache, batch, prefill_tile, cfg,
              interpret: Optional[bool] = None):
    """One Gated DeltaNet mixer over the flat token buffer of a decode
    step or a two-segment (tiled) batch.  ``la``: the mixer's parameters;
    ``xn`` [T, hidden]: what the projections read (the caller's normed
    stream, or the raw one).  Returns ``(out [T, hidden], {"state",
    "conv"})``.  ``cfg.linear_allow_neg_eigval``: write strengths ``2
    sigmoid(b)`` in (0, 2), so a state's eigenvalues along a key reach
    -1; ``interpret``: as ``gdn_step`` takes it."""
    dt = cfg.dtype
    hk, hv = cfg.linear_num_key_heads, cfg.linear_num_value_heads
    dk, dv = cfg.linear_key_head_dim, cfg.linear_value_head_dim
    conv_dim = gdn_conv_dim(cfg)
    pool = layer_cache["state"]
    scratch = pool.shape[0] - 1
    pos, sslot = batch["token_pos"], batch["state_slot"]
    t_rows, s_rows = xn.shape[0], sslot.shape[0]
    with jax.named_scope("gdn/in_proj"):
        qkvz = qmm(xn, la["in_proj_qkvz"]["kernel"], dt)
        ba = qmm(xn, la["in_proj_ba"]["kernel"], dt)
        u, z = qkvz[:, :conv_dim], qkvz[:, conv_dim:]
    with jax.named_scope("gdn/conv"):
        u, conv = _causal_conv(u, la["conv1d"]["kernel"],
                               layer_cache["conv"], batch,
                               prefill_tile=prefill_tile)
    with jax.named_scope("gdn/rule"):
        u32 = u.astype(F32)

        def unit(y):            # L2 norm per head, as published
            return y * jax.lax.rsqrt(
                jnp.sum(y * y, axis=-1, keepdims=True) + 1e-6)

        q = unit(u32[:, :hk * dk].reshape(t_rows, hk, dk)) * dk ** -0.5
        k = unit(u32[:, hk * dk:2 * hk * dk].reshape(t_rows, hk, dk))
        v = u32[:, 2 * hk * dk:].reshape(t_rows, hv, dv)
        if hv != hk:            # each key head serves hv // hk value heads
            q = jnp.repeat(q, hv // hk, axis=1)
            k = jnp.repeat(k, hv // hk, axis=1)
        real = (pos >= 0)[:, None]
        beta = jax.nn.sigmoid(ba[:, :hv].astype(F32))
        if getattr(cfg, "linear_allow_neg_eigval", False):
            beta = 2.0 * beta
        beta = jnp.where(real, beta, 0.)
        g = jnp.where(real, -jnp.exp(la["A_log"].astype(F32))
                      * jax.nn.softplus(ba[:, hv:].astype(F32)
                                        + la["dt_bias"].astype(F32)), 0.)
        rows = slice(0, s_rows)             # one token a row
        row_slot = jnp.where(pos[rows] >= 0,
                             sslot[batch["token_slot"][rows]], scratch)
        o, pool = gdn_step(pool, q[rows], k[rows], v[rows], g[rows],
                           beta[rows], row_slot, pos[rows] == 0,
                           interpret=interpret)
        if t_rows > s_rows:                 # the tile segment
            rows = slice(s_rows, t_rows)
            first = slice(s_rows, t_rows, int(prefill_tile))
            tile_slot = jnp.where(pos[first] >= 0,
                                  sslot[batch["token_slot"][first]],
                                  scratch)
            o2, pool = gdn_chunk(pool, q[rows], k[rows], v[rows],
                                 g[rows], beta[rows], tile_slot,
                                 pos[first] == 0, int(prefill_tile),
                                 interpret=interpret)
            o = jnp.concatenate([o, o2])
    with jax.named_scope("gdn/out"):
        # RMSNorm per head with a plain weight, gated by silu(z)
        o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True)
                              + cfg.rms_norm_eps)
        o = o * la["norm"]["scale"].astype(F32) \
            * _silu(z.astype(F32).reshape(t_rows, hv, dv))
        out = qmm(o.astype(dt).reshape(t_rows, hv * dv),
                  la["out_proj"]["kernel"], dt)
    return out, {"state": pool, "conv": conv}
