"""Plain reference for the Olmo-Hybrid block (Olmo-Hybrid-7B as published,
``model_type: olmo_hybrid``).  ``h`` is the residual stream; layer ``l`` is
what ``layer_types[l]`` says (published: ``full_attention`` where ``l % 4 ==
3``, ``linear_attention`` elsewhere).  ``RMS_n(x; w) = x * rsqrt(mean_n(x^2)
+ eps) * w`` (a plain weight).  The block is POST-norm: every sub-layer reads
the raw stream and its output is normalised before it is added::

    h <- h + RMS(mixer(h); post_attention_layernorm)
    h <- h + RMS((silu(h W_gate) * h W_up) W_down; post_feedforward_layernorm)
    logits = RMS(h; norm) W_head                      (after the last layer)

*Linear attention (Gated DeltaNet)*, ``H`` heads of ``dk`` keys x ``dv``
values.  ``q | k | v | z = h W_qkvz``, ``b | a = h W_ba``; ``q | k | v`` pass
a causal depthwise convolution of ``linear_conv_kernel_dim`` taps (no bias,
the last tap on the current token) and SiLU; per head ``q = c_q *
rsqrt(|c_q|^2 + 1e-6) * dk^-0.5``, ``k = c_k * rsqrt(|c_k|^2 + 1e-6)``;
``beta = 2 sigmoid(b)`` when ``linear_allow_neg_eigval`` (in (0, 2)) and
``sigmoid(b)`` otherwise; ``g = -exp(A_log) softplus(a + dt_bias)``.  Per
head a float32 state ``S [dk, dv]``, zero before the first token, and
**token by token** (a plain ``lax.scan`` over the tokens, not the chunked
form the program computes)::

    S *= exp(g_t);  d = (v_t - S^T k_t) * beta_t;  S += k_t d^T;  o_t = S^T q_t

then per head ``y = RMS_dv(o; w_norm) * silu(z)`` and ``out_proj``.

*Full attention*, multi-head (30 query = 30 KV heads of 128): ``q =
RMS(h W_q; q_norm)`` and ``k = RMS(h W_k; k_norm)`` over the WHOLE
projection, before the head split; ``v = h W_v``; NO positional embedding;
causal softmax of ``q . k * head_dim^-0.5``; ``o_proj``.

What ``config.json`` does not state, and is ASSUMED here as in the program
and in the configuration file's ``assumed``: (a) the block's order and the
whole-projection q/k norms are the OLMo 2 / OLMo 3 convention
(``transformers/models/olmo3/modeling_olmo3.py``); (b) no rotary, because
``rope_parameters.rope_theta`` is null (a non-null one is refused); (c) the
mixer's internals are the Gated DeltaNet's as Qwen3-Next publishes it under
the same key names (``modeling_qwen3_next.py``), the ``z`` gate through a
per-head RMSNorm included; (d) the projections' packing ``q | k | v | z`` and
``b | a``.  No other departure.

Straightforward float32 ``jax.numpy`` under
``default_matmul_precision("highest")``, layer by layer, no kernels, no
cache, no batching, and no import from ``deepspeed_tpu``.  One sequence at a
time; the head is applied in blocks of the vocabulary so that its float32
copy never stands whole beside the program.

Parameters are a plain dict the family adapter builds: ``{"embed": [V, H],
"layers": [...], "norm": [H], "lm_head": [H, V]}``; a linear layer is
``{"w_qkvz" [H, 2 Hk dk + 2 Hv dv], "w_ba" [H, 2 Hv], "conv" [taps, 2 Hk dk
+ Hv dv], "A_log" [Hv], "dt_bias" [Hv], "gnorm" [dv], "wo"}``, an attention
layer ``{"wq", "wk", "wv", "wo", "q_norm" [Hq D], "k_norm" [Hkv D]}``, and
both carry ``"post_attn" [H], "post_ff" [H], "w_gate", "w_up" [H, F],
"w_down" [F, H]``; every matrix [in, out].
"""

from __future__ import annotations

import functools
from typing import Dict, Sequence

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
KINDS = ("linear_attention", "full_attention")
#: columns of the head multiplied at once
VOCAB_BLOCK = 16384


def _rms(x, w, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w


def _silu(x):
    return x * jax.nn.sigmoid(x)


def _attention(q, k, v, q_block):
    """q, k, v: [S, H, D] -> [S, H, D]; causal, softmax in float32, one
    block of query rows at a time."""
    s, h, d = q.shape
    kpos = jnp.arange(s)
    nblk = -(-s // q_block)
    qp = jnp.pad(q, ((0, nblk * q_block - s), (0, 0), (0, 0)))

    def block(i):
        qs = jax.lax.dynamic_slice_in_dim(qp, i * q_block, q_block, 0)
        qpos = i * q_block + jnp.arange(q_block)
        sc = jnp.einsum("qhd,shd->hqs", qs, k) / np.sqrt(d)
        keep = kpos[None, :] <= qpos[:, None]
        sc = jnp.where(keep[None], sc, -jnp.inf)
        return jnp.einsum("hqs,shd->qhd", jax.nn.softmax(sc, axis=-1), v)

    out = jax.lax.map(block, jnp.arange(nblk))
    return out.reshape(nblk * q_block, h, d)[:s]


def _attn_mixer(x, lp, *, hq, eps, q_block):
    s = x.shape[0]
    d = lp["wq"].shape[1] // hq
    q = _rms(x @ lp["wq"], lp["q_norm"], eps).reshape(s, hq, d)
    k = _rms(x @ lp["wk"], lp["k_norm"], eps).reshape(s, hq, d)
    v = (x @ lp["wv"]).reshape(s, hq, d)
    return _attention(q, k, v, q_block).reshape(s, hq * d) @ lp["wo"]


def _gdn_mixer(x, lp, *, hk, hv, eps, neg_eigval):
    s = x.shape[0]
    dv = lp["gnorm"].shape[0]
    conv_dim = lp["conv"].shape[1]
    dk = (conv_dim - hv * dv) // (2 * hk)
    qkvz = x @ lp["w_qkvz"]
    ba = x @ lp["w_ba"]
    u, z = qkvz[:, :conv_dim], qkvz[:, conv_dim:]
    taps = lp["conv"].shape[0]
    up = jnp.pad(u, ((taps - 1, 0), (0, 0)))
    u = _silu(sum(up[j:j + s] * lp["conv"][j] for j in range(taps)))
    q = u[:, :hk * dk].reshape(s, hk, dk)
    k = u[:, hk * dk:2 * hk * dk].reshape(s, hk, dk)
    v = u[:, 2 * hk * dk:].reshape(s, hv, dv)
    unit = lambda y: y * jax.lax.rsqrt(
        jnp.sum(y * y, axis=-1, keepdims=True) + 1e-6)
    q = jnp.repeat(unit(q) * dk ** -0.5, hv // hk, axis=1)
    k = jnp.repeat(unit(k), hv // hk, axis=1)
    beta = jax.nn.sigmoid(ba[:, :hv]) * (2.0 if neg_eigval else 1.0)
    g = -jnp.exp(lp["A_log"]) * jax.nn.softplus(ba[:, hv:] + lp["dt_bias"])

    def token(st, xs):                  # st: [Hv, dk, dv]
        q_t, k_t, v_t, g_t, b_t = xs
        st = st * jnp.exp(g_t)[:, None, None]
        d = (v_t - jnp.einsum("hkv,hk->hv", st, k_t)) * b_t[:, None]
        st = st + k_t[:, :, None] * d[:, None, :]
        return st, jnp.einsum("hkv,hk->hv", st, q_t)

    _, o = jax.lax.scan(token, jnp.zeros((hv, dk, dv), F32),
                        (q, k, v, g, beta))
    o = _rms(o, lp["gnorm"], eps) * _silu(z.reshape(s, hv, dv))
    return o.reshape(s, hv * dv) @ lp["wo"]


@functools.partial(jax.jit, static_argnames=(
    "hq", "hk", "hv", "eps", "neg_eigval", "q_block"))
def _layer(x, lp, *, hq, hk, hv, eps, neg_eigval, q_block):
    with jax.default_matmul_precision("highest"):
        lp = jax.tree.map(lambda a: a.astype(F32), lp)
        if "wq" in lp:
            m = _attn_mixer(x, lp, hq=hq, eps=eps, q_block=q_block)
        else:
            m = _gdn_mixer(x, lp, hk=hk, hv=hv, eps=eps,
                           neg_eigval=neg_eigval)
        x = x + _rms(m, lp["post_attn"], eps)
        f = (_silu(x @ lp["w_gate"]) * (x @ lp["w_up"])) @ lp["w_down"]
        return x + _rms(f, lp["post_ff"], eps)


@jax.jit
def _embed(table, ids):
    return table[ids].astype(F32)


@functools.partial(jax.jit, static_argnames=("eps",))
def _normed(x, norm, *, eps):
    return _rms(x, norm.astype(F32), eps)


@jax.jit
def _head_block(x, w):
    with jax.default_matmul_precision("highest"):
        return x @ w.astype(F32)


def _check(cfg: Dict) -> None:
    kinds = cfg.get("layer_types")
    if kinds is None or len(kinds) != int(cfg["num_hidden_layers"]) \
            or any(k not in KINDS for k in kinds):
        raise ValueError(f"layer_types must name one of {KINDS} for each of "
                         f"num_hidden_layers layers, got {kinds!r}")
    if (cfg.get("rope_parameters") or {}).get("rope_theta") is not None:
        raise ValueError("reference/olmo_hybrid.py implements the published "
                         "attention without positions: rope_parameters."
                         "rope_theta must be null")
    if cfg.get("attention_bias") or cfg.get("tie_word_embeddings") \
            or int(cfg["num_key_value_heads"]) != int(
                cfg["num_attention_heads"]):
        raise ValueError("reference/olmo_hybrid.py implements the published "
                         "block: no attention bias, an untied head, "
                         "multi-head attention")


def hidden(params: Dict, ids: np.ndarray, cfg: Dict,
           q_block: int = 512) -> jnp.ndarray:
    """The residual stream [S, H] after the last layer of ONE sequence."""
    _check(cfg)
    s = int(ids.shape[0])
    x = _embed(params["embed"], np.asarray(ids, np.int32))
    for kind, lp in zip(cfg["layer_types"], params["layers"]):
        if ("wq" in lp) != (kind == "full_attention"):
            raise ValueError(f"a {kind} layer was given "
                             f"{sorted(lp)} as its parameters")
        x = _layer(x, lp, hq=int(cfg["num_attention_heads"]),
                   hk=int(cfg["linear_num_key_heads"]),
                   hv=int(cfg["linear_num_value_heads"]),
                   eps=float(cfg["rms_norm_eps"]),
                   neg_eigval=bool(cfg["linear_allow_neg_eigval"]),
                   q_block=min(q_block, s))
    return x


def logits_at(params: Dict, ids: np.ndarray, cfg: Dict,
              rows: Sequence[int], q_block: int = 512) -> np.ndarray:
    """Next-token logits [len(rows), vocab] of ONE sequence ``ids`` [S]
    after a full forward pass, at the given positions."""
    x = _normed(hidden(params, ids, cfg, q_block)[np.asarray(rows)],
                params["norm"], eps=float(cfg["rms_norm_eps"]))
    head = params["lm_head"]
    return np.concatenate([
        np.asarray(_head_block(x, head[:, c:c + VOCAB_BLOCK]), np.float32)
        for c in range(0, head.shape[1], VOCAB_BLOCK)], axis=1)
