"""Plain reference for the Ouro looped language model (``model_type: ouro``
as published by ByteDance: Ouro-2.6B, ``modeling_ouro.py``): ONE stack of
Llama-shaped layers with a norm before AND after each branch, run
``total_ut_steps`` times a token with the same weights, the final norm
applied after every pass and fed to the next, an exit gate on each pass's
normed hidden state, an untied head after the last pass.

*Block* (layer ``l``, the same in every pass).  ``a = n(h; w_in)``; ``q, k, v
= a W_q, a W_k, a W_v`` (no bias); q and k rotated (rotate-half over the whole
head, ``rope_theta``, no scaling); causal attention, scores ``q . k *
head_dim^-0.5``, softmax in float32, each KV head serving ``Hq / Hkv`` query
heads; ``h' = h + n(o W_o; w_in2)``; ``m = n(h'; w_post)``; ``h'' = h' +
n((silu(m W_g) * (m W_u)) W_d; w_post2)`` with ``n(x; w) = x / rms(x) * w``
(``rms_norm_eps``).

*Loop.*  ``h_0 = E[ids]``; pass ``t``: ``h = stack(h)`` then ``h = n(h;
w_final)``, which is both what pass ``t + 1`` starts from and the state
``h_t`` the gate and (after the last pass) the head read.  In a served model
each (layer, pass) keeps keys and values of its own; a full forward has no
cache, so that is simply what each pass computes from its own input.

*Exit gate.*  ``lambda_t = sigmoid(h_t w_exit + b_exit)``; the exit
distribution ``p_t = lambda_t prod_{j<t} (1 - lambda_j)`` for every pass but
the last, which takes what is left (``prod_{j<T-1} (1 - lambda_j)``), so ``p``
sums to 1.  With the published ``early_exit_threshold`` of 1 the cumulative
``p`` never reaches the threshold before the last pass: every token runs
every pass, and the logits are those of the last.  A threshold below 1 is
refused.

Straightforward float32 ``jax.numpy`` under
``default_matmul_precision("highest")``, layer by layer, no kernels, no
cache, no batching, and no import from ``deepspeed_tpu``.  One sequence at a
time; attention in blocks of query rows against the whole context; a layer's
parameters are converted to float32 inside its call, a layer at a time
(51 M parameters = 0.2 GB at the published widths), never the model's 2.7 B.

Parameters are a plain dict the family adapter builds: ``{"embed": [V, H],
"layers": [{"ln_in", "ln_in2", "ln_post", "ln_post2", "wq", "wk", "wv", "wo",
"w_gate", "w_up", "w_down"}, ...], "norm": [H], "exit_w": [H, 1], "exit_b":
[1], "lm_head": [H, V]}``, every matrix stored [in, out].
"""

from __future__ import annotations

import functools
from typing import Dict, List, Sequence

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
        sc = jnp.where((kpos[None, :] <= qpos[:, None])[None, None], sc,
                       -jnp.inf)
        return jnp.einsum("kgqs,skd->qkgd", jax.nn.softmax(sc, axis=-1), v)

    out = jax.lax.map(block, jnp.arange(nblk))
    return out.reshape(nblk * q_block, hq * d)[:s]


@functools.partial(jax.jit, static_argnames=("hq", "hkv", "eps", "theta",
                                             "q_block"))
def _layer(x, lp, *, hq, hkv, eps, theta, q_block):
    with jax.default_matmul_precision("highest"):
        lp = jax.tree.map(lambda a: a.astype(F32), lp)
        s = x.shape[0]
        pos = jnp.arange(s)
        a = _rms(x, lp["ln_in"], eps)
        d = lp["wq"].shape[1] // hq
        q = _rope((a @ lp["wq"]).reshape(s, hq, d), pos, theta)
        k = _rope((a @ lp["wk"]).reshape(s, hkv, d), pos, theta)
        v = (a @ lp["wv"]).reshape(s, hkv, d)
        x = x + _rms(_attention(q, k, v, q_block) @ lp["wo"], lp["ln_in2"],
                     eps)
        m = _rms(x, lp["ln_post"], eps)
        f = (jax.nn.silu(m @ lp["w_gate"]) * (m @ lp["w_up"])) @ lp["w_down"]
        return x + _rms(f, lp["ln_post2"], eps)


@jax.jit
def _embed(table, ids):
    return table[ids].astype(F32)


@functools.partial(jax.jit, static_argnames=("eps",))
def _norm(x, w, *, eps):
    return _rms(x, w, eps)


@jax.jit
def _head(x, lm_head):
    with jax.default_matmul_precision("highest"):
        return x @ lm_head.astype(F32)


def _check(cfg: Dict) -> None:
    if float(cfg.get("early_exit_threshold", 1)) < 1:
        raise NotImplementedError(
            "early_exit_threshold < 1: tokens that leave the loop early "
            "are not part of this reference")
    if cfg.get("rope_scaling") is not None or cfg.get("sliding_window") \
            is not None or cfg.get("use_sliding_window"):
        raise NotImplementedError(
            "rope_scaling and a sliding window are not part of this "
            "reference (the published configuration has neither)")


def pass_hiddens(params: Dict, ids: np.ndarray, cfg: Dict,
                 q_block: int = 512) -> List[jax.Array]:
    """``[h_0, ..., h_{T-1}]``: the normed hidden state ``[S, hidden]`` of
    ONE sequence ``ids`` [S] after each pass of the stack."""
    _check(cfg)
    s = int(ids.shape[0])
    eps = float(cfg["rms_norm_eps"])
    x = _embed(params["embed"], np.asarray(ids, np.int32))
    out = []
    for _ in range(int(cfg["total_ut_steps"])):
        for lp in params["layers"]:
            x = _layer(x, lp, hq=int(cfg["num_attention_heads"]),
                       hkv=int(cfg["num_key_value_heads"]), eps=eps,
                       theta=float(cfg["rope_theta"]),
                       q_block=min(q_block, s))
        x = _norm(x, params["norm"], eps=eps)
        out.append(x)
    return out


def logits_at(params: Dict, ids: np.ndarray, cfg: Dict,
              rows: Sequence[int], q_block: int = 512) -> np.ndarray:
    """Next-token logits [len(rows), vocab] of ONE sequence ``ids`` [S]
    after a full forward pass (every pass of the stack), at the given
    positions."""
    x = pass_hiddens(params, ids, cfg, q_block)[-1][np.asarray(rows)]
    return np.asarray(_head(x, params["lm_head"]), np.float32)


def exit_distribution(params: Dict, hiddens) -> Dict[str, np.ndarray]:
    """The exit gate over the passes, from ``hiddens`` ``[T, rows, hidden]``
    (or the list :func:`pass_hiddens` returns): ``{"lambda": [T, rows], "p":
    [T, rows]}`` as the module doc defines them, in float32."""
    with jax.default_matmul_precision("highest"):
        h = jnp.stack([jnp.asarray(x, F32) for x in hiddens])
        lam = jax.nn.sigmoid(h @ params["exit_w"].astype(F32)[:, 0]
                             + params["exit_b"].astype(F32)[0])
    lam = np.asarray(lam, np.float32)
    p, left = [], np.ones_like(lam[0])
    for t in range(lam.shape[0] - 1):
        p.append(lam[t] * left)
        left = left * (1.0 - lam[t])
    p.append(left)
    return {"lambda": lam, "p": np.stack(p)}
