"""Plain reference for the dense Jamba block (``model_type: jamba`` with
``num_experts`` 1, as published by AI21: AI21-Jamba2-3B): pre-norm RMSNorm
with a plain weight (``rms_norm_eps``); the mixer of layer ``l`` is
grouped-query softmax attention where ``l % attn_layer_period ==
attn_layer_offset`` and a Mamba mixer elsewhere; the FFN of every layer a
dense SwiGLU (no router at one expert); one more norm after the last layer
and the head tied to the embedding.  No positional embedding anywhere.

*Mamba mixer* (``Di = mamba_expand x hidden_size`` channels, ``N =
mamba_d_state``, ``R = mamba_dt_rank``, ``K = mamba_d_conv`` taps)::

    [x | z] = a W_in
    x_t = silu(b_c + sum_{j<K} w_c[j] * x_{t-K+1+j})    (depthwise, zeros before 0)
    [dt_r | B | C] = x W_x;  dt_r, B, C = rms(dt_r; g_dt), rms(B; g_b), rms(C; g_c)
    dt = softplus(dt_r W_dt + b_dt);  A = -exp(A_log)
    s_t = exp(dt_t[:, None] * A) * s_{t-1} + (dt_t * x_t)[:, None] * B_t[None, :]
    y_t = s_t C_t + D * x_t
    out = (y * silu(z)) W_out

with ``s [Di, N]`` float32 and zero before the first token, computed
**token by token** (a plain ``lax.scan`` over the tokens, one after
another, as written: no chunking, no associative scan, no cache).

*Attention.*  ``q, k, v = a W_q, a W_k, a W_v``, no bias, NO rotation;
scores ``q . k * head_dim^-0.5``, causal softmax in float32, each KV head
serving ``Hq / Hkv`` query heads; ``out = concat(o) W_o``.

Straightforward float32 ``jax.numpy`` under
``default_matmul_precision("highest")``, layer by layer, no kernels, no
cache, no batching, and no import from ``deepspeed_tpu``.  One sequence at
a time; a layer's matrices are converted to float32 inside that layer's
call, so 3 B parameters never stand whole in float32.

Parameters are a plain dict the family adapter builds: ``{"embed": [V, H],
"layers": [{"ln1", "ln2", "gate", "up", "down", then either "w_in" [H, 2
Di], "taps" [K, Di], "conv_bias" [Di], "w_x" [Di, R + 2 N], "w_dt" [R, Di],
"b_dt" [Di], "A_log" [Di, N], "D" [Di], "g_dt" [R], "g_b" [N], "g_c" [N],
"w_out" [Di, H] (Mamba) or "wq", "wk", "wv", "wo" (attention)}, ...],
"norm": [H]}``, every matrix stored [in, out]; the head is ``embed``
transposed.
"""

from __future__ import annotations

import functools
from typing import Dict, Sequence

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32

MAMBA_KEYS = ("ln1", "w_in", "taps", "conv_bias", "w_x", "w_dt", "b_dt",
              "A_log", "D", "g_dt", "g_b", "g_c", "w_out")
ATTN_KEYS = ("ln1", "wq", "wk", "wv", "wo")
FFN_KEYS = ("ln2", "gate", "up", "down")


def _rms(x, w, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w.astype(F32)


def _silu(x):
    return x * jax.nn.sigmoid(x)


def mamba(h, lp, *, eps):
    """The Mamba mixer of one sequence: ``h`` [S, H] (normed) -> [S, H]."""
    x, z = jnp.split(h @ lp["w_in"], 2, axis=-1)
    s_len, taps = x.shape[0], lp["taps"].shape[0]
    padded = jnp.pad(x, ((taps - 1, 0), (0, 0)))        # zeros before 0
    x = _silu(lp["conv_bias"] + sum(lp["taps"][j] * padded[j:j + s_len]
                                    for j in range(taps)))
    r, n = lp["g_dt"].shape[0], lp["g_b"].shape[0]
    dbc = x @ lp["w_x"]
    dt_r = _rms(dbc[:, :r], lp["g_dt"], eps)
    b = _rms(dbc[:, r:r + n], lp["g_b"], eps)
    c = _rms(dbc[:, r + n:], lp["g_c"], eps)
    dt = jax.nn.softplus(dt_r @ lp["w_dt"] + lp["b_dt"])
    a = -jnp.exp(lp["A_log"])                           # [Di, N]

    def token(s, row):
        dt_t, x_t, b_t, c_t = row
        s = jnp.exp(dt_t[:, None] * a) * s \
            + (dt_t * x_t)[:, None] * b_t[None, :]
        return s, s @ c_t

    _, y = jax.lax.scan(token, jnp.zeros_like(a), (dt, x, b, c))
    return ((y + lp["D"] * x) * _silu(z)) @ lp["w_out"]


def attention(h, lp, *, hq, hkv, d, q_block):
    """Causal grouped-query attention of one sequence, no positions: ``h``
    [S, H] (normed) -> [S, H]."""
    s = h.shape[0]
    pos = jnp.arange(s)
    q = (h @ lp["wq"]).reshape(s, hq, d)
    k = (h @ lp["wk"]).reshape(s, hkv, d)
    v = (h @ lp["wv"]).reshape(s, hkv, d)
    g = hq // hkv
    outs = []
    for r0 in range(0, s, q_block):
        qb = q[r0:r0 + q_block].reshape(-1, hkv, g, d)
        sc = jnp.einsum("qkgd,ckd->kgqc", qb, k) * d ** -0.5
        keep = pos[None, :] <= pos[r0:r0 + q_block, None]
        p = jax.nn.softmax(jnp.where(keep[None, None], sc, -jnp.inf), -1)
        outs.append(jnp.einsum("kgqc,ckd->qkgd", p, v).reshape(-1, hq * d))
    return jnp.concatenate(outs) @ lp["wo"]


@functools.partial(jax.jit, static_argnames=("eps",))
def _mamba_layer(x, lp, *, eps):
    with jax.default_matmul_precision("highest"):
        lp = jax.tree.map(lambda a: a.astype(F32), lp)
        return x + mamba(_rms(x, lp["ln1"], eps), lp, eps=eps)


@functools.partial(jax.jit, static_argnames=("hq", "hkv", "d", "eps",
                                             "q_block"))
def _attn_layer(x, lp, *, hq, hkv, d, eps, q_block):
    with jax.default_matmul_precision("highest"):
        lp = jax.tree.map(lambda a: a.astype(F32), lp)
        return x + attention(_rms(x, lp["ln1"], eps), lp, hq=hq, hkv=hkv,
                             d=d, q_block=q_block)


@functools.partial(jax.jit, static_argnames=("eps",))
def _ffn(x, lp, *, eps):
    with jax.default_matmul_precision("highest"):
        lp = jax.tree.map(lambda a: a.astype(F32), lp)
        h = _rms(x, lp["ln2"], eps)
        return x + (_silu(h @ lp["gate"]) * (h @ lp["up"])) @ lp["down"]


@jax.jit
def _embed(table, ids):
    return table[ids].astype(F32)


@functools.partial(jax.jit, static_argnames=("eps",))
def _logits(x, norm, embed, *, eps):
    with jax.default_matmul_precision("highest"):
        return _rms(x, norm, eps) @ embed.astype(F32).T


def _check(cfg: Dict) -> None:
    if int(cfg.get("num_experts", 1)) > 1 or cfg.get("mamba_proj_bias") \
            or cfg.get("sliding_window") is not None \
            or not cfg.get("tie_word_embeddings", True):
        raise ValueError("reference/jamba.py implements the published dense "
                         "Jamba block: one expert (no router), no bias in "
                         "the Mamba projections, no window, a tied head")


def head_dim(cfg: Dict) -> int:
    return int(cfg["hidden_size"]) // int(cfg["num_attention_heads"])


def hidden(params: Dict, ids: np.ndarray, cfg: Dict,
           q_block: int = 512) -> jnp.ndarray:
    """The residual stream [S, H] after the last layer of ONE sequence."""
    _check(cfg)
    s = int(ids.shape[0])
    eps = float(cfg["rms_norm_eps"])
    x = _embed(params["embed"], np.asarray(ids, np.int32))
    for lp in params["layers"]:
        if "taps" in lp:
            x = _mamba_layer(x, {k: lp[k] for k in MAMBA_KEYS}, eps=eps)
        else:
            x = _attn_layer(
                x, {k: lp[k] for k in ATTN_KEYS},
                hq=int(cfg["num_attention_heads"]),
                hkv=int(cfg["num_key_value_heads"]), d=head_dim(cfg),
                eps=eps, q_block=min(q_block, s))
        x = _ffn(x, {k: lp[k] for k in FFN_KEYS}, eps=eps)
    return x


def logits_at(params: Dict, ids: np.ndarray, cfg: Dict,
              rows: Sequence[int], q_block: int = 512) -> np.ndarray:
    """Next-token logits [len(rows), vocab] of ONE sequence ``ids`` [S]
    after a full forward pass, at the given positions."""
    x = hidden(params, ids, cfg, q_block)[np.asarray(rows)]
    return np.asarray(_logits(x, params["norm"], params["embed"],
                              eps=float(cfg["rms_norm_eps"])), np.float32)
