"""Plain reference for the LFM2-MoE block (``model_type: lfm2_moe`` as
published by LiquidAI: LFM2-24B-A2B): pre-norm RMSNorm with a plain weight
(``norm_eps``); the mixer of layer ``i`` is grouped-query softmax attention
where ``layer_types[i] == "full_attention"`` and a gated short convolution
elsewhere; the FFN a dense SwiGLU in the first ``num_dense_layers`` layers
and routed experts behind a sigmoid router with a selection bias in the
rest, no shared expert; one more norm after the last layer and the head
tied to the embedding.

*Gated short convolution* (``conv_L_cache`` K).  ``[B | C | u] = x W_in``
(three blocks of ``hidden_size`` columns, in that order); ``g = B * u``;
``c_t = sum_{j<K} w[j] * g_{t-K+1+j}``, depthwise over the channels, zeros
before position 0, the LAST tap on the current token; no activation, no
bias; ``y = (C * c) W_out``.  Computed here over the whole sequence with a
padded shift: no cache, no state.

*Attention.*  ``q, k, v = x W_q, x W_k, x W_v``; per head ``q = rms(q)``,
``k = rms(k)`` with plain weights of ``head_dim`` BEFORE the rotation;
rotary over the whole head in the rotate-half form, ``rope_theta``; scores
``q . k * head_dim^-0.5``, causal softmax in float32, each KV head serving
``Hq / Hkv`` query heads; ``out = concat(o) W_o``.

*Router.*  ``s = sigmoid(x W_g)`` over all experts; the chosen experts are
the top-k of ``s + b`` (``expert_bias``); their weights are ``s`` at those
experts (without ``b``), divided by (their sum + ``router_norm_eps``, the
published code's 1e-6) when ``norm_topk_prob``, times
``routed_scaling_factor``.  ``y = sum_k w_k E_k(x)``, ``E`` a SwiGLU of
width ``moe_intermediate_size``.

Straightforward float32 ``jax.numpy`` under
``default_matmul_precision("highest")``, layer by layer, no kernels, no
cache, no batching, and no import from ``deepspeed_tpu``.  One sequence at
a time; attention in blocks of query rows against the whole context; the
experts by a plain loop with a mask, converted to float32 ``expert_block``
at a time (8 of 64: 0.30 GB at the published widths, never 2.4 GB).

Parameters are a plain dict the family adapter builds: ``{"embed": [V, H],
"layers": [{"ln1", "ln2", then either "w_in" [H, 3H], "taps" [K, H],
"w_out" [H, H] (convolution) or "wq", "wk", "wv", "wo", "q_norm", "k_norm"
(attention), then either "gate", "up", "down" (dense) or "router" [H, E],
"bias" [E], "w_gate" [E, H, F], "w_up", "w_down" [E, F, H]}, ...], "norm":
[H]}``, every matrix stored [in, out]; the head is ``embed`` transposed.
"""

from __future__ import annotations

import functools
from typing import Dict, Sequence

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32


def _rms(x, w, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w.astype(F32)


def _silu(x):
    return x * jax.nn.sigmoid(x)


def short_conv(h, lp):
    """The gated short convolution of one sequence: ``h`` [S, H] (normed)
    -> [S, H]."""
    b, c, u = jnp.split(h @ lp["w_in"], 3, axis=-1)
    g = b * u
    s, taps = g.shape[0], lp["taps"].shape[0]
    padded = jnp.pad(g, ((taps - 1, 0), (0, 0)))        # zeros before 0
    conv = sum(lp["taps"][j] * padded[j:j + s] for j in range(taps))
    return (c * conv) @ lp["w_out"]


def _rope(x, pos, theta):
    """Rotate-half rotary over the whole head: x [S, heads, D]."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=F32) / d))
    ang = pos.astype(F32)[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(h, lp, *, hq, hkv, d, eps, theta, q_block):
    """Causal grouped-query attention of one sequence: ``h`` [S, H]
    (normed) -> [S, H]."""
    s = h.shape[0]
    pos = jnp.arange(s)
    q = _rms((h @ lp["wq"]).reshape(s, hq, d), lp["q_norm"], eps)
    k = _rms((h @ lp["wk"]).reshape(s, hkv, d), lp["k_norm"], eps)
    v = (h @ lp["wv"]).reshape(s, hkv, d)
    q, k = _rope(q, pos, theta), _rope(k, pos, theta)
    g = hq // hkv
    outs = []
    for r0 in range(0, s, q_block):
        qb = q[r0:r0 + q_block].reshape(-1, hkv, g, d)
        sc = jnp.einsum("qkgd,ckd->kgqc", qb, k) * d ** -0.5
        keep = pos[None, :] <= pos[r0:r0 + q_block, None]
        p = jax.nn.softmax(jnp.where(keep[None, None], sc, -jnp.inf), -1)
        outs.append(jnp.einsum("kgqc,ckd->qkgd", p, v).reshape(-1, hq * d))
    return jnp.concatenate(outs) @ lp["wo"]


def route(h, router, bias, top_k, norm_topk, scale, norm_eps):
    """(expert ids [S, k], weights [S, k]) of the sigmoid router."""
    s = jax.nn.sigmoid(h @ router)
    _, idx = jax.lax.top_k(s + bias, top_k)
    w = jnp.take_along_axis(s, idx, axis=-1)
    if norm_topk:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + norm_eps)
    return idx, w * scale


def routed(h, lp, *, top_k, norm_topk, scale, norm_eps, expert_start):
    """What the experts of ``lp`` (ids from ``expert_start``) add."""
    idx, w = route(h, lp["router"], lp["bias"], top_k, norm_topk, scale,
                   norm_eps)

    def one(acc, e):
        y = (_silu(h @ lp["w_gate"][e]) * (h @ lp["w_up"][e])) \
            @ lp["w_down"][e]
        p_e = jnp.sum(jnp.where(idx == e + expert_start, w, 0.0), axis=-1)
        return acc + p_e[:, None] * y, None

    out, _ = jax.lax.scan(one, jnp.zeros_like(h),
                          jnp.arange(lp["w_gate"].shape[0]))
    return out


@functools.partial(jax.jit, static_argnames=("eps",))
def _conv_layer(x, lp, *, eps):
    with jax.default_matmul_precision("highest"):
        lp = jax.tree.map(lambda a: a.astype(F32), lp)
        return x + short_conv(_rms(x, lp["ln1"], eps), lp)


@functools.partial(jax.jit, static_argnames=(
    "hq", "hkv", "d", "eps", "theta", "q_block"))
def _attn_layer(x, lp, *, hq, hkv, d, eps, theta, q_block):
    with jax.default_matmul_precision("highest"):
        lp = jax.tree.map(lambda a: a.astype(F32), lp)
        return x + attention(_rms(x, lp["ln1"], eps), lp, hq=hq, hkv=hkv,
                             d=d, eps=eps, theta=theta, q_block=q_block)


@functools.partial(jax.jit, static_argnames=("eps",))
def _dense_ffn(x, lp, *, eps):
    with jax.default_matmul_precision("highest"):
        lp = jax.tree.map(lambda a: a.astype(F32), lp)
        h = _rms(x, lp["ln2"], eps)
        return x + (_silu(h @ lp["gate"]) * (h @ lp["up"])) @ lp["down"]


@functools.partial(jax.jit, static_argnames=(
    "eps", "top_k", "norm_topk", "scale", "norm_eps"))
def _moe_block(x, acc, lp, block, expert_start, *, eps, top_k, norm_topk,
               scale, norm_eps):
    """``acc`` plus what one block of the experts (``block``: their
    matrices; ``expert_start``: the id of its first) gives."""
    with jax.default_matmul_precision("highest"):
        lp, block = jax.tree.map(lambda a: a.astype(F32), (lp, block))
        h = _rms(x, lp["ln2"], eps)
        return acc + routed(h, {**lp, **block}, top_k=top_k,
                            norm_topk=norm_topk, scale=scale,
                            norm_eps=norm_eps, expert_start=expert_start)


@jax.jit
def _embed(table, ids):
    return table[ids].astype(F32)


@functools.partial(jax.jit, static_argnames=("eps",))
def _logits(x, norm, embed, *, eps):
    with jax.default_matmul_precision("highest"):
        return _rms(x, norm, eps) @ embed.astype(F32).T


def _check(cfg: Dict) -> None:
    if cfg.get("conv_bias") or not cfg.get("use_expert_bias", True) \
            or (cfg.get("rope_parameters") or {}).get(
                "rope_type", "default") != "default":
        raise ValueError("reference/lfm2_moe.py implements the published "
                         "LFM2-MoE block: no convolution bias, the router's "
                         "selection bias, plain rotary")


def head_dim(cfg: Dict) -> int:
    return int(cfg.get("head_dim") or
               cfg["hidden_size"] // cfg["num_attention_heads"])


_EXPERT_KEYS = ("w_gate", "w_up", "w_down")


def hidden(params: Dict, ids: np.ndarray, cfg: Dict, q_block: int = 512,
           expert_block: int = 8) -> jnp.ndarray:
    """The residual stream [S, H] after the last layer of ONE sequence."""
    _check(cfg)
    s = int(ids.shape[0])
    eps = float(cfg["norm_eps"])
    theta = float(cfg["rope_parameters"]["rope_theta"])
    x = _embed(params["embed"], np.asarray(ids, np.int32))
    for lp in params["layers"]:
        if "taps" in lp:
            x = _conv_layer(x, {k: lp[k] for k in ("ln1", "w_in", "taps",
                                                   "w_out")}, eps=eps)
        else:
            x = _attn_layer(
                x, {k: lp[k] for k in ("ln1", "wq", "wk", "wv", "wo",
                                       "q_norm", "k_norm")},
                hq=int(cfg["num_attention_heads"]),
                hkv=int(cfg["num_key_value_heads"]), d=head_dim(cfg),
                eps=eps, theta=theta, q_block=min(q_block, s))
        if "router" not in lp:
            x = _dense_ffn(x, {k: lp[k] for k in ("ln2", "gate", "up",
                                                  "down")}, eps=eps)
            continue
        small = {k: lp[k] for k in ("ln2", "router", "bias")}
        acc = jnp.zeros_like(x)
        for e0 in range(0, lp["w_gate"].shape[0], expert_block):
            acc = _moe_block(
                x, acc, small,
                {k: lp[k][e0:e0 + expert_block] for k in _EXPERT_KEYS}, e0,
                eps=eps, top_k=int(cfg["num_experts_per_tok"]),
                norm_topk=bool(cfg.get("norm_topk_prob", True)),
                scale=float(cfg.get("routed_scaling_factor", 1.0)),
                norm_eps=float(cfg.get("router_norm_eps", 1e-6)))
        x = x + acc
    return x


def logits_at(params: Dict, ids: np.ndarray, cfg: Dict,
              rows: Sequence[int], q_block: int = 512) -> np.ndarray:
    """Next-token logits [len(rows), vocab] of ONE sequence ``ids`` [S]
    after a full forward pass, at the given positions."""
    x = hidden(params, ids, cfg, q_block)[np.asarray(rows)]
    return np.asarray(_logits(x, params["norm"], params["embed"],
                              eps=float(cfg["norm_eps"])), np.float32)
