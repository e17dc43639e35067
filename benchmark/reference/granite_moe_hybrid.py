"""Plain reference for the Granite-4.0-H block (``model_type:
granitemoehybrid``, as published by IBM: ibm-granite/granite-4.0-h-small):
pre-norm RMSNorm with a plain weight (``rms_norm_eps``); the mixer of layer
``l`` is what ``layer_types[l]`` says, a Mamba-2 mixer (``mamba``) or
grouped-query softmax attention with NO positional embedding
(``attention``); the FFN of EVERY layer is ``num_experts_per_tok`` routed
experts of ``router_experts`` beside one shared expert; one more norm after
the last layer and the head tied to the embedding.  Four scalars::

    h0 = embedding_multiplier * E[ids]
    h  = h + residual_multiplier * mixer(RMSNorm(h; ln1))
    h  = h + residual_multiplier * (moe(u) + shared(u)),    u = RMSNorm(h; ln2)
    logits = (RMSNorm(h_L; norm) E^T) / logits_scaling

*Mamba-2 mixer* (``H = mamba_n_heads`` heads of ``P = mamba_d_head``
channels, ``Di = H P``, ``N = mamba_d_state``, one group, ``K =
mamba_d_conv`` taps)::

    [z | xBC | dt_raw] = a W_in                      widths Di | Di + 2 N | H
    xBC_t = silu(b_c + sum_{j<K} w_c[j] * xBC_{t-K+1+j})    (depthwise, zeros before 0)
    [X | B | C] = xBC;  X as [H, P]
    dt = softplus(dt_raw + dt_bias) [H];  A = -exp(A_log) [H]
    S_t = exp(dt_t A)[:, None, None] * S_{t-1} + (dt_t[:, None] * X_t)[:, :, None] * B_t[None, None, :]
    Y_t = S_t C_t + D[:, None] * X_t
    y = RMSNorm(Y * silu(z); g)          the gate first, ONE norm over all Di
    out = y W_out

with ``S [H, P, N]`` float32 and zero before the first token, computed
**token by token** (a plain ``lax.scan`` over the tokens, one after another,
as written: no chunking, no matmul form, no cache).

*Attention.*  ``q, k, v = a W_q, a W_k, a W_v``, no bias, NO rotation;
scores ``q . k * attention_multiplier`` (1/128 as published, NOT
``head_dim^-0.5``), causal softmax in float32, each KV head serving ``Hq /
Hkv`` query heads; ``out = concat(o) W_o``.

*FFN.*  ``logits = u W_r`` over all ``router_experts``; the top-k of the
LOGITS; ``g = softmax`` over those k; ``moe = sum_k g_k e_k(u)`` with ``e(u)
= (silu(u W_gate) * (u W_up)) W_down``, of which only the experts HELD here
(``[expert_start, expert_start + held)``, held read from the matrices) are
added; ``shared(u)`` the same form at ``shared_intermediate_size``, always
added.

Straightforward float32 ``jax.numpy`` under
``default_matmul_precision("highest")``, layer by layer, no kernels, no
cache, no batching, and no import from ``deepspeed_tpu``.  One sequence at
a time; a layer's matrices are converted to float32 inside that layer's
call (the experts one at a time), so 3 B parameters never stand whole in
float32.

Parameters are a plain dict the family adapter builds: ``{"embed": [V, H],
"layers": [{"ln1", "ln2", "router" [H, E], "w_gate" / "w_up" [e, H, F],
"w_down" [e, F, H], "s_gate" / "s_up" [H, Fs], "s_down" [Fs, H], then either
"w_in" [H, 2 Di + 2 N + Hm], "taps" [K, Di + 2 N], "conv_bias" [Di + 2 N],
"dt_bias", "A_log", "D" [Hm], "gnorm" [Di], "w_out" [Di, H] (Mamba-2) or
"wq", "wk", "wv", "wo" (attention)}, ...], "norm": [H]}``, every matrix
stored [in, out]; the head is ``embed`` transposed.
"""

from __future__ import annotations

import functools
from typing import Dict, Sequence

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32

MAMBA_KEYS = ("ln1", "w_in", "taps", "conv_bias", "dt_bias", "A_log", "D",
              "gnorm", "w_out")
ATTN_KEYS = ("ln1", "wq", "wk", "wv", "wo")
FFN_KEYS = ("ln2", "router", "w_gate", "w_up", "w_down", "s_gate", "s_up",
            "s_down")


def _rms(x, w, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w.astype(F32)


def _silu(x):
    return x * jax.nn.sigmoid(x)


def mamba2(h, lp, *, n, eps):
    """The Mamba-2 mixer of one sequence: ``h`` [S, H] (normed) -> [S,
    H]."""
    heads, di = lp["A_log"].shape[0], lp["w_out"].shape[0]
    p = di // heads
    proj = h @ lp["w_in"]
    z, xbc, dt_raw = proj[:, :di], proj[:, di:2 * di + 2 * n], \
        proj[:, 2 * di + 2 * n:]
    s_len, taps = xbc.shape[0], lp["taps"].shape[0]
    padded = jnp.pad(xbc, ((taps - 1, 0), (0, 0)))      # zeros before 0
    xbc = _silu(lp["conv_bias"] + sum(lp["taps"][j] * padded[j:j + s_len]
                                      for j in range(taps)))
    x = xbc[:, :di].reshape(s_len, heads, p)
    b, c = xbc[:, di:di + n], xbc[:, di + n:]
    dt = jax.nn.softplus(dt_raw + lp["dt_bias"])        # [S, H]
    a = -jnp.exp(lp["A_log"])                           # [H]

    def token(s, row):
        dt_t, x_t, b_t, c_t = row
        s = jnp.exp(dt_t * a)[:, None, None] * s \
            + (dt_t[:, None] * x_t)[:, :, None] * b_t[None, None, :]
        return s, s @ c_t + lp["D"][:, None] * x_t

    _, y = jax.lax.scan(token, jnp.zeros((heads, p, n), F32), (dt, x, b, c))
    y = _rms(y.reshape(s_len, di) * _silu(z), lp["gnorm"], eps)
    return y @ lp["w_out"]


def attention(h, lp, *, hq, hkv, d, scale, q_block):
    """Causal grouped-query attention of one sequence, no positions, the
    scores times ``scale``: ``h`` [S, H] (normed) -> [S, H]."""
    s = h.shape[0]
    pos = jnp.arange(s)
    q = (h @ lp["wq"]).reshape(s, hq, d)
    k = (h @ lp["wk"]).reshape(s, hkv, d)
    v = (h @ lp["wv"]).reshape(s, hkv, d)
    g = hq // hkv
    outs = []
    for r0 in range(0, s, q_block):
        qb = q[r0:r0 + q_block].reshape(-1, hkv, g, d)
        sc = jnp.einsum("qkgd,ckd->kgqc", qb, k) * scale
        keep = pos[None, :] <= pos[r0:r0 + q_block, None]
        p = jax.nn.softmax(jnp.where(keep[None, None], sc, -jnp.inf), -1)
        outs.append(jnp.einsum("kgqc,ckd->qkgd", p, v).reshape(-1, hq * d))
    return jnp.concatenate(outs) @ lp["wo"]


def route(u, router, top_k: int):
    """(indices [S, k], weights [S, k]): the top-k of the router's LOGITS,
    then a softmax over those k."""
    vals, idx = jax.lax.top_k(u @ router, top_k)
    return idx, jax.nn.softmax(vals, axis=-1)


def ffn(u, lp, *, top_k, expert_start):
    """Routed experts held here plus the shared expert: ``u`` [S, H]
    (normed) -> [S, H]."""
    idx, w = route(u, lp["router"].astype(F32), top_k)

    def one(acc, e):                    # e: index among the HELD experts
        y = (_silu(u @ lp["w_gate"][e].astype(F32))
             * (u @ lp["w_up"][e].astype(F32))) @ lp["w_down"][e].astype(F32)
        g_e = jnp.sum(jnp.where(idx == e + expert_start, w, 0.0), axis=-1)
        return acc + g_e[:, None] * y, None

    out, _ = jax.lax.scan(one, jnp.zeros_like(u),
                          jnp.arange(lp["w_gate"].shape[0]))
    shared = (_silu(u @ lp["s_gate"].astype(F32))
              * (u @ lp["s_up"].astype(F32))) @ lp["s_down"].astype(F32)
    return out + shared


@functools.partial(jax.jit, static_argnames=("n", "eps", "rm"))
def _mamba_layer(x, lp, *, n, eps, rm):
    with jax.default_matmul_precision("highest"):
        lp = jax.tree.map(lambda a: a.astype(F32), lp)
        return x + rm * mamba2(_rms(x, lp["ln1"], eps), lp, n=n, eps=eps)


@functools.partial(jax.jit, static_argnames=("hq", "hkv", "d", "scale", "eps",
                                             "rm", "q_block"))
def _attn_layer(x, lp, *, hq, hkv, d, scale, eps, rm, q_block):
    with jax.default_matmul_precision("highest"):
        lp = jax.tree.map(lambda a: a.astype(F32), lp)
        return x + rm * attention(_rms(x, lp["ln1"], eps), lp, hq=hq,
                                  hkv=hkv, d=d, scale=scale, q_block=q_block)


@functools.partial(jax.jit, static_argnames=("top_k", "expert_start", "eps",
                                             "rm"))
def _ffn_layer(x, lp, *, top_k, expert_start, eps, rm):
    with jax.default_matmul_precision("highest"):
        return x + rm * ffn(_rms(x, lp["ln2"], eps), lp, top_k=top_k,
                            expert_start=expert_start)


@functools.partial(jax.jit, static_argnames=("mult",))
def _embed(table, ids, *, mult):
    return mult * table[ids].astype(F32)


@functools.partial(jax.jit, static_argnames=("eps", "scaling"))
def _logits(x, norm, embed, *, eps, scaling):
    with jax.default_matmul_precision("highest"):
        return _rms(x, norm, eps) @ embed.astype(F32).T / scaling


def _check(cfg: Dict) -> None:
    if int(cfg.get("mamba_n_groups", 1)) != 1 or cfg.get("mamba_proj_bias") \
            or cfg.get("attention_bias") \
            or cfg.get("position_embedding_type", "nope") != "nope" \
            or not cfg.get("tie_word_embeddings", True):
        raise ValueError("reference/granite_moe_hybrid.py implements the "
                         "published Granite-4.0-H block: one group, no bias "
                         "in the projections, no positional embedding, a "
                         "tied head")


def head_dim(cfg: Dict) -> int:
    return int(cfg["hidden_size"]) // int(cfg["num_attention_heads"])


def hidden(params: Dict, ids: np.ndarray, cfg: Dict,
           q_block: int = 512) -> jnp.ndarray:
    """The residual stream [S, H] after the last layer of ONE sequence."""
    _check(cfg)
    s = int(ids.shape[0])
    eps, rm = float(cfg["rms_norm_eps"]), float(cfg["residual_multiplier"])
    x = _embed(params["embed"], np.asarray(ids, np.int32),
               mult=float(cfg["embedding_multiplier"]))
    for lp in params["layers"]:
        if "taps" in lp:
            x = _mamba_layer(x, {k: lp[k] for k in MAMBA_KEYS},
                             n=int(cfg["mamba_d_state"]), eps=eps, rm=rm)
        else:
            x = _attn_layer(
                x, {k: lp[k] for k in ATTN_KEYS},
                hq=int(cfg["num_attention_heads"]),
                hkv=int(cfg["num_key_value_heads"]), d=head_dim(cfg),
                scale=float(cfg["attention_multiplier"]), eps=eps, rm=rm,
                q_block=min(q_block, s))
        x = _ffn_layer(x, {k: lp[k] for k in FFN_KEYS},
                       top_k=int(cfg["num_experts_per_tok"]),
                       expert_start=int(cfg.get("expert_start", 0)),
                       eps=eps, rm=rm)
    return x


def logits_at(params: Dict, ids: np.ndarray, cfg: Dict,
              rows: Sequence[int], q_block: int = 512) -> np.ndarray:
    """Next-token logits [len(rows), vocab] of ONE sequence ``ids`` [S]
    after a full forward pass, at the given positions."""
    x = hidden(params, ids, cfg, q_block)[np.asarray(rows)]
    return np.asarray(_logits(x, params["norm"], params["embed"],
                              eps=float(cfg["rms_norm_eps"]),
                              scaling=float(cfg["logits_scaling"])),
                      np.float32)
