"""Plain reference for the Mistral / Llama block (Mistral-7B-v0.1 as
published: RMSNorm, rotary embeddings in the rotate-half form, grouped-query
causal attention with a sliding window, SwiGLU MLP, untied lm_head).

Straightforward float32 ``jax.numpy`` under
``default_matmul_precision("highest")`` (on a TPU a float32 matmul otherwise
runs in bf16 passes), layer by layer, no kernels, no cache, no batching, and
no import from ``deepspeed_tpu``.  One sequence at a time; attention in
blocks of query rows against the whole context so a 4096-token sequence
never materialises an [H, S, S] tensor.

Parameters are a plain dict the family adapter builds:
``{"embed": [V, H], "layers": [{"ln1", "ln2", "wq", "wk", "wv", "wo",
"w_gate", "w_up", "w_down"}, ...], "norm": [H], "lm_head": [H, V]}`` with
every matrix stored [in, out].
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


def _rope(x, pos, theta):
    """x: [S, H, D]; rotate-half: (x1, x2) -> (x1 cos - x2 sin, x2 cos +
    x1 sin) with x1/x2 the two halves of the head dimension."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=F32) / d))
    ang = pos.astype(F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(q, k, v, window, q_block):
    """q: [S, Hq, D], k/v: [S, Hkv, D] -> [S, Hq*D]; causal, optional
    sliding window, softmax in float32, one block of query rows at a time."""
    s, hq, d = q.shape
    hkv = k.shape[1]
    qg = q.reshape(s, hkv, hq // hkv, d)
    kpos = jnp.arange(s)
    nblk = -(-s // q_block)
    pad = nblk * q_block - s
    qg = jnp.pad(qg, ((0, pad), (0, 0), (0, 0), (0, 0)))

    def block(i):
        qs = jax.lax.dynamic_slice_in_dim(qg, i * q_block, q_block, 0)
        qpos = i * q_block + jnp.arange(q_block)
        sc = jnp.einsum("qkgd,skd->kgqs", qs, k) / np.sqrt(d)
        keep = kpos[None, :] <= qpos[:, None]
        if window is not None:
            keep &= kpos[None, :] > qpos[:, None] - window
        sc = jnp.where(keep[None, None], sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        return jnp.einsum("kgqs,skd->qkgd", p, v)

    out = jax.lax.map(block, jnp.arange(nblk))
    return out.reshape(nblk * q_block, hq * d)[:s]


@functools.partial(jax.jit, static_argnames=("hq", "hkv", "eps", "theta",
                                             "window", "q_block"))
def _layer(x, lp, *, hq, hkv, eps, theta, window, q_block):
    with jax.default_matmul_precision("highest"):
        lp = jax.tree.map(lambda a: a.astype(F32), lp)
        s = x.shape[0]
        pos = jnp.arange(s)
        h = _rms(x, lp["ln1"], eps)
        d = lp["wq"].shape[1] // hq
        q = _rope((h @ lp["wq"]).reshape(s, hq, d), pos, theta)
        k = _rope((h @ lp["wk"]).reshape(s, hkv, d), pos, theta)
        v = (h @ lp["wv"]).reshape(s, hkv, d)
        x = x + _attention(q, k, v, window, q_block) @ lp["wo"]
        h = _rms(x, lp["ln2"], eps)
        return x + (jax.nn.silu(h @ lp["w_gate"]) * (h @ lp["w_up"])) \
            @ lp["w_down"]


@jax.jit
def _embed(table, ids):
    return table[ids].astype(F32)


@functools.partial(jax.jit, static_argnames=("eps",))
def _logits(x, norm, lm_head, *, eps):
    with jax.default_matmul_precision("highest"):
        return _rms(x, norm, eps) @ lm_head.astype(F32)


def _hidden(params: Dict, ids: np.ndarray, cfg: Dict, q_block: int):
    s = int(ids.shape[0])
    window = cfg.get("sliding_window")
    window = int(window) if window is not None and s > int(window) else None
    x = _embed(params["embed"], np.asarray(ids, np.int32))
    for lp in params["layers"]:
        x = _layer(x, lp, hq=int(cfg["num_attention_heads"]),
                   hkv=int(cfg["num_key_value_heads"]),
                   eps=float(cfg["rms_norm_eps"]),
                   theta=float(cfg["rope_theta"]), window=window,
                   q_block=min(q_block, s))
    return x


def logits_at(params: Dict, ids: np.ndarray, cfg: Dict,
              rows: Sequence[int], q_block: int = 512) -> np.ndarray:
    """Next-token logits [len(rows), vocab] of ONE sequence ``ids`` [S]
    after a full forward pass, at the given positions."""
    x = _hidden(params, ids, cfg, q_block)[np.asarray(rows)]
    return np.asarray(_logits(x, params["norm"], params["lm_head"],
                              eps=float(cfg["rms_norm_eps"])), np.float32)


@jax.jit
def _nll_sum(logits, targets):
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, targets[:, None], axis=-1)[:, 0]
    return jnp.sum(logz - gold)


def loss(params: Dict, ids: np.ndarray, cfg: Dict,
         q_block: int = 512) -> float:
    """Mean next-token cross-entropy over a batch ``ids`` [B, S] (labels =
    inputs shifted by one, every position counted)."""
    total, count = 0.0, 0
    for seq in np.asarray(ids):
        x = _hidden(params, seq, cfg, q_block)
        lg = _logits(x[:-1], params["norm"], params["lm_head"],
                     eps=float(cfg["rms_norm_eps"]))
        total += float(_nll_sum(lg, np.asarray(seq[1:], np.int32)))
        count += len(seq) - 1
    return total / count
