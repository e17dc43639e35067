"""Plain reference for the OLMoE block (OLMoE-1B-7B-0125-Instruct as
published, ``model_type: olmoe``): pre-norm RMSNorm, RMSNorm over the WHOLE
q and k projections (all heads at once) before the head split and the
rotary embedding, rotary embeddings in the rotate-half form, causal
multi-head attention (as many KV heads as query heads in the published
config; grouped heads are handled), a router ``softmax(x @ W_g)`` in
float32 over all experts, the top-k of those probabilities NOT renormalised
(``norm_topk_prob: false``), SwiGLU experts without bias, output
``sum_e p_e * down_e(silu(gate_e x) * up_e x)``, untied lm_head.

Departures from the published block, all of them absent settings:
``clip_qkv`` is null in the published config and is not implemented (a
config that sets it is refused); no attention bias; no rope scaling; no
sliding window; the router's auxiliary and z losses belong to training and
are not computed.

Straightforward float32 ``jax.numpy`` under
``default_matmul_precision("highest")`` (on a TPU a float32 matmul otherwise
runs in bf16 passes), layer by layer, no kernels, no cache, no batching, and
no import from ``deepspeed_tpu``.  One sequence at a time; attention in
blocks of query rows against the whole context; **every expert is computed
for the tokens routed to it by a plain loop over the experts with a mask**:
expert ``e`` sees every token, and a token keeps ``p_e`` times the result
only if ``e`` is among its top-k (a weight of exactly zero otherwise).

Parameters are a plain dict the family adapter builds:
``{"embed": [V, H], "layers": [{"ln1", "ln2", "wq", "wk", "wv", "wo",
"q_norm" [Hq*D], "k_norm" [Hkv*D], "router" [H, E], "w_gate" [E, H, F],
"w_up" [E, H, F], "w_down" [E, F, H]}, ...], "norm": [H], "lm_head":
[H, V]}`` with every matrix stored [in, out].
"""

from __future__ import annotations

import functools
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32


def _rms(x, w, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w.astype(F32)


def _rope(x, pos, theta):
    """x: [S, H, D]; rotate-half: (x1, x2) -> (x1 cos - x2 sin, x2 cos +
    x1 sin) with x1/x2 the two halves of the head dimension."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=F32) / d))
    ang = pos.astype(F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(q, k, v, q_block):
    """q: [S, Hq, D], k/v: [S, Hkv, D] -> [S, Hq*D]; causal, softmax in
    float32, one block of query rows at a time."""
    s, hq, d = q.shape
    hkv = k.shape[1]
    qg = q.reshape(s, hkv, hq // hkv, d)
    kpos = jnp.arange(s)
    nblk = -(-s // q_block)
    qg = jnp.pad(qg, ((0, nblk * q_block - s), (0, 0), (0, 0), (0, 0)))

    def block(i):
        qs = jax.lax.dynamic_slice_in_dim(qg, i * q_block, q_block, 0)
        qpos = i * q_block + jnp.arange(q_block)
        sc = jnp.einsum("qkgd,skd->kgqs", qs, k) / np.sqrt(d)
        keep = kpos[None, :] <= qpos[:, None]
        sc = jnp.where(keep[None, None], sc, -jnp.inf)
        return jnp.einsum("kgqs,skd->qkgd", jax.nn.softmax(sc, axis=-1), v)

    out = jax.lax.map(block, jnp.arange(nblk))
    return out.reshape(nblk * q_block, hq * d)[:s]


def route(h, router, top_k: int, norm_topk: bool):
    """h: [S, H] (the normalised residual) -> (experts [S, k] int32,
    weights [S, k] float32): softmax over ALL experts in float32, the
    top-k probabilities, renormalised only when ``norm_topk``."""
    probs = jax.nn.softmax(h.astype(F32) @ router.astype(F32), axis=-1)
    w, idx = jax.lax.top_k(probs, top_k)
    if norm_topk:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    return idx.astype(jnp.int32), w


def _experts(h, idx, w, lp):
    """Plain loop over the experts: each computes its SwiGLU FFN on every
    token, and a token keeps weight x result only for its own experts."""
    n_experts = lp["w_gate"].shape[0]

    def one(acc, e):
        y = (jax.nn.silu(h @ lp["w_gate"][e]) * (h @ lp["w_up"][e])) \
            @ lp["w_down"][e]
        p_e = jnp.sum(jnp.where(idx == e, w, 0.0), axis=-1)      # [S]
        return acc + p_e[:, None] * y, None

    out, _ = jax.lax.scan(one, jnp.zeros_like(h), jnp.arange(n_experts))
    return out


@functools.partial(jax.jit, static_argnames=("hq", "hkv", "eps", "theta",
                                             "top_k", "norm_topk",
                                             "q_block"))
def _layer(x, lp, *, hq, hkv, eps, theta, top_k, norm_topk, q_block):
    with jax.default_matmul_precision("highest"):
        lp = jax.tree.map(lambda a: a.astype(F32), lp)
        s = x.shape[0]
        pos = jnp.arange(s)
        h = _rms(x, lp["ln1"], eps)
        d = lp["wq"].shape[1] // hq
        q = _rms(h @ lp["wq"], lp["q_norm"], eps)     # over all heads
        k = _rms(h @ lp["wk"], lp["k_norm"], eps)
        q = _rope(q.reshape(s, hq, d), pos, theta)
        k = _rope(k.reshape(s, hkv, d), pos, theta)
        v = (h @ lp["wv"]).reshape(s, hkv, d)
        x = x + _attention(q, k, v, q_block) @ lp["wo"]
        h = _rms(x, lp["ln2"], eps)
        idx, w = route(h, lp["router"], top_k, norm_topk)
        return x + _experts(h, idx, w, lp), idx


@jax.jit
def _embed(table, ids):
    return table[ids].astype(F32)


@functools.partial(jax.jit, static_argnames=("eps",))
def _logits(x, norm, lm_head, *, eps):
    with jax.default_matmul_precision("highest"):
        return _rms(x, norm, eps) @ lm_head.astype(F32)


def _check(cfg: Dict) -> None:
    if cfg.get("clip_qkv") is not None or cfg.get("attention_bias") or \
            cfg.get("rope_scaling") is not None:
        raise ValueError("reference/olmoe.py implements the published "
                         "OLMoE-1B-7B block: clip_qkv, attention_bias and "
                         "rope_scaling must be unset")


def _hidden(params: Dict, ids: np.ndarray, cfg: Dict, q_block: int,
            routings: Optional[List] = None):
    _check(cfg)
    s = int(ids.shape[0])
    x = _embed(params["embed"], np.asarray(ids, np.int32))
    for lp in params["layers"]:
        x, idx = _layer(x, lp, hq=int(cfg["num_attention_heads"]),
                        hkv=int(cfg["num_key_value_heads"]),
                        eps=float(cfg["rms_norm_eps"]),
                        theta=float(cfg["rope_theta"]),
                        top_k=int(cfg["num_experts_per_tok"]),
                        norm_topk=bool(cfg.get("norm_topk_prob", False)),
                        q_block=min(q_block, s))
        if routings is not None:
            routings.append(np.asarray(idx))
    return x


def logits_at(params: Dict, ids: np.ndarray, cfg: Dict,
              rows: Sequence[int], q_block: int = 512) -> np.ndarray:
    """Next-token logits [len(rows), vocab] of ONE sequence ``ids`` [S]
    after a full forward pass, at the given positions."""
    x = _hidden(params, ids, cfg, q_block)[np.asarray(rows)]
    return np.asarray(_logits(x, params["norm"], params["lm_head"],
                              eps=float(cfg["rms_norm_eps"])), np.float32)


def routings(params: Dict, ids: np.ndarray, cfg: Dict,
             q_block: int = 512) -> np.ndarray:
    """The experts every token of ``ids`` was routed to in every layer,
    [layers, S, k]: what a lower-precision forward is compared with to
    say how many routings it flipped."""
    out: List = []
    _hidden(params, ids, cfg, q_block, out)
    return np.stack(out)
