"""Plain reference for the Qwen3-Next block (Qwen3-Next-80B-A3B-Instruct as
published, ``model_type: qwen3_next``).  ``x`` is the residual stream; layer
``i`` (0-based) is full attention when ``(i + 1) % full_attention_interval
== 0`` and Gated DeltaNet otherwise; every layer does ``x += mixer(norm(x));
x += moe(norm(x))`` with ``norm(x) = x * rsqrt(mean(x^2) + eps) * (1 + w)``
(a zero-centred weight; the final norm and the per-head q/k norms too).

*Gated DeltaNet.*  ``q | k | v | z = x W_qkvz``, ``b | a = x W_ba``;
``q | k | v`` pass a causal depthwise convolution of ``linear_conv_kernel_dim``
taps (no bias) and SiLU; ``beta = sigmoid(b)``, ``g = -exp(A_log) *
softplus(a + dt_bias)``; q and k are L2-normalised per head (eps 1e-6), each
key head serves ``value_heads / key_heads`` value heads, ``q *= dk^-0.5``.
Per value head a float32 state ``S [dk, dv]``, zero before the first token,
and **token by token** (a plain ``lax.scan`` over the tokens, not the
chunked form the program computes)::

    S *= exp(g_t);  d = (v_t - S^T k_t) * beta_t;  S += k_t d^T;  o_t = S^T q_t

then per head ``o = w_n * o * rsqrt(mean(o^2) + eps) * silu(z)`` (a plain
weight) and ``out_proj``.

*Gated attention.*  ``q_proj`` gives, per head, the query and a gate;
RMSNorm per head on q and k (``1 + w``); rotary (rotate-half) on the first
``partial_rotary_factor`` of each head; causal softmax attention scaled
``head_dim^-0.5``; ``o * sigmoid(gate)``; ``o_proj``.

*MoE.*  Router ``softmax(x W_g)`` in float32 over ALL ``num_experts``,
top-k, renormalised when ``norm_topk_prob``; experts ``down(silu(gate x) *
up x)``; plus ``sigmoid(x . w_sg) * shared_expert(x)``.  **The share**: the
parameter dict may hold fewer experts than the router has outputs
(``w_gate [E_held, ...]``, experts ``expert_start ...`` of the router's);
then only those are summed, by a plain loop over them with a mask, and what
the others would add is left out, as in the program.

Departures from the published model: the multi-token-prediction module the
model card mentions has no key in ``config.json`` and is left out; no
attention bias, no rope scaling, no sliding window (a config that sets one
is refused).

Straightforward float32 ``jax.numpy`` under
``default_matmul_precision("highest")``, layer by layer, no kernels, no
cache, no batching, and no import from ``deepspeed_tpu``.  One sequence at a
time.

Parameters are a plain dict the family adapter builds: ``{"embed": [V, H],
"layers": [...], "norm": [H], "lm_head": [H, V]}``; a DeltaNet layer is
``{"ln1", "ln2", "w_qkvz" [H, 2 Hk dk + 2 Hv dv] (columns q | k | v | z),
"w_ba" [H, 2 Hv] (b | a), "conv" [taps, 2 Hk dk + Hv dv] (last tap on the
current token), "A_log" [Hv], "dt_bias" [Hv], "gnorm" [dv], "wo"}``, an
attention layer ``{"ln1", "ln2", "wq" [H, Hq 2 D] (per head: query | gate),
"wk", "wv", "wo", "q_norm" [D], "k_norm" [D]}``, and both carry ``"router"
[H, E], "w_gate" [E_held, H, F], "w_up", "w_down" [E_held, F, H], "s_gate",
"s_up" [H, Fs], "s_down" [Fs, H], "s_sg" [H, 1]``; every matrix [in, out].
"""

from __future__ import annotations

import functools
from typing import Dict, Sequence

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32


def _norm1p(x, w, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + w.astype(F32))


def _silu(x):
    return x * jax.nn.sigmoid(x)


def _rope_partial(x, pos, theta, rot):
    """x: [S, H, D]; rotate-half over the first ``rot`` dims of each head."""
    inv = 1.0 / (theta ** (jnp.arange(0, rot, 2, dtype=F32) / rot))
    ang = pos.astype(F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2, rest = x[..., :rot // 2], x[..., rot // 2:rot], x[..., rot:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest],
                           -1)


def _attention(q, k, v, q_block):
    """q: [S, Hq, D], k/v: [S, Hkv, D] -> [S, Hq, D]; causal, softmax in
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
    return out.reshape(nblk * q_block, hq, d)[:s]


def _attn_mixer(h, lp, *, hq, hkv, eps, theta, rot, q_block):
    s = h.shape[0]
    d = lp["wk"].shape[1] // hkv
    qg = (h @ lp["wq"]).reshape(s, hq, 2 * d)
    q, gate = qg[..., :d], qg[..., d:]
    k = (h @ lp["wk"]).reshape(s, hkv, d)
    v = (h @ lp["wv"]).reshape(s, hkv, d)
    pos = jnp.arange(s)
    q = _rope_partial(_norm1p(q, lp["q_norm"], eps), pos, theta, rot)
    k = _rope_partial(_norm1p(k, lp["k_norm"], eps), pos, theta, rot)
    o = _attention(q, k, v, q_block) * jax.nn.sigmoid(gate)
    return o.reshape(s, hq * d) @ lp["wo"]


def _gdn_mixer(h, lp, *, hk, hv, eps):
    s = h.shape[0]
    dv = lp["gnorm"].shape[0]
    conv_dim = lp["conv"].shape[1]
    dk = (conv_dim - hv * dv) // (2 * hk)
    qkvz = h @ lp["w_qkvz"]
    ba = h @ lp["w_ba"]
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
    beta = jax.nn.sigmoid(ba[:, :hv])
    g = -jnp.exp(lp["A_log"]) * jax.nn.softplus(ba[:, hv:] + lp["dt_bias"])

    def token(st, xs):                  # st: [Hv, dk, dv]
        q_t, k_t, v_t, g_t, b_t = xs
        st = st * jnp.exp(g_t)[:, None, None]
        d = (v_t - jnp.einsum("hkv,hk->hv", st, k_t)) * b_t[:, None]
        st = st + k_t[:, :, None] * d[:, None, :]
        return st, jnp.einsum("hkv,hk->hv", st, q_t)

    _, o = jax.lax.scan(token, jnp.zeros((hv, dk, dv), F32),
                        (q, k, v, g, beta))
    o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + eps)
    o = o * lp["gnorm"] * _silu(z.reshape(s, hv, dv))
    return o.reshape(s, hv * dv) @ lp["wo"]


def route(h, router, top_k: int, norm_topk: bool):
    """h: [S, H] (the normalised residual) -> (experts [S, k] int32,
    weights [S, k] float32): softmax over ALL experts in float32, the
    top-k probabilities, renormalised when ``norm_topk``."""
    probs = jax.nn.softmax(h.astype(F32) @ router.astype(F32), axis=-1)
    w, idx = jax.lax.top_k(probs, top_k)
    if norm_topk:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    return idx.astype(jnp.int32), w


def _moe(h, lp, *, top_k, norm_topk, expert_start):
    idx, w = route(h, lp["router"], top_k, norm_topk)

    def one(acc, e):                    # e: index among the HELD experts
        y = (_silu(h @ lp["w_gate"][e]) * (h @ lp["w_up"][e])) \
            @ lp["w_down"][e]
        p_e = jnp.sum(jnp.where(idx == e + expert_start, w, 0.0), axis=-1)
        return acc + p_e[:, None] * y, None

    out, _ = jax.lax.scan(one, jnp.zeros_like(h),
                          jnp.arange(lp["w_gate"].shape[0]))
    shared = (_silu(h @ lp["s_gate"]) * (h @ lp["s_up"])) @ lp["s_down"]
    return out + jax.nn.sigmoid(h @ lp["s_sg"]) * shared


@functools.partial(jax.jit, static_argnames=(
    "hq", "hkv", "hk", "hv", "eps", "theta", "rot", "top_k", "norm_topk",
    "expert_start", "q_block"))
def _layer(x, lp, *, hq, hkv, hk, hv, eps, theta, rot, top_k, norm_topk,
           expert_start, q_block):
    with jax.default_matmul_precision("highest"):
        lp = jax.tree.map(lambda a: a.astype(F32), lp)
        h = _norm1p(x, lp["ln1"], eps)
        if "wq" in lp:
            x = x + _attn_mixer(h, lp, hq=hq, hkv=hkv, eps=eps, theta=theta,
                                rot=rot, q_block=q_block)
        else:
            x = x + _gdn_mixer(h, lp, hk=hk, hv=hv, eps=eps)
        return x + _moe(_norm1p(x, lp["ln2"], eps), lp, top_k=top_k,
                        norm_topk=norm_topk, expert_start=expert_start)


@jax.jit
def _embed(table, ids):
    return table[ids].astype(F32)


@functools.partial(jax.jit, static_argnames=("eps",))
def _logits(x, norm, lm_head, *, eps):
    with jax.default_matmul_precision("highest"):
        return _norm1p(x, norm, eps) @ lm_head.astype(F32)


def _check(cfg: Dict) -> None:
    if cfg.get("attention_bias") or cfg.get("rope_scaling") is not None \
            or cfg.get("sliding_window") is not None:
        raise ValueError("reference/qwen3_next.py implements the published "
                         "Qwen3-Next block: attention_bias, rope_scaling "
                         "and sliding_window must be unset")
    for i, kind in enumerate(cfg.get("layer_types") or ()):
        want = "full_attention" if (i + 1) % int(
            cfg["full_attention_interval"]) == 0 else "linear_attention"
        if kind != want:
            raise ValueError(f"layer_types[{i}] = {kind!r}: the reference "
                             f"lays layers out by full_attention_interval")


def hidden(params: Dict, ids: np.ndarray, cfg: Dict,
           q_block: int = 512) -> jnp.ndarray:
    """The residual stream [S, H] after the last layer of ONE sequence."""
    _check(cfg)
    s = int(ids.shape[0])
    x = _embed(params["embed"], np.asarray(ids, np.int32))
    d = int(cfg["head_dim"])
    for lp in params["layers"]:
        x = _layer(x, lp, hq=int(cfg["num_attention_heads"]),
                   hkv=int(cfg["num_key_value_heads"]),
                   hk=int(cfg["linear_num_key_heads"]),
                   hv=int(cfg["linear_num_value_heads"]),
                   eps=float(cfg["rms_norm_eps"]),
                   theta=float(cfg["rope_theta"]),
                   rot=int(d * float(cfg["partial_rotary_factor"])),
                   top_k=int(cfg["num_experts_per_tok"]),
                   norm_topk=bool(cfg.get("norm_topk_prob", True)),
                   expert_start=int(cfg.get("expert_start", 0)),
                   q_block=min(q_block, s))
    return x


def logits_at(params: Dict, ids: np.ndarray, cfg: Dict,
              rows: Sequence[int], q_block: int = 512) -> np.ndarray:
    """Next-token logits [len(rows), vocab] of ONE sequence ``ids`` [S]
    after a full forward pass, at the given positions."""
    x = hidden(params, ids, cfg, q_block)[np.asarray(rows)]
    return np.asarray(_logits(x, params["norm"], params["lm_head"],
                              eps=float(cfg["rms_norm_eps"])), np.float32)
