"""Plain reference for the AFMoE block (``model_type: afmoe`` as published by
arcee-ai: Trinity-Large-Preview, ``modeling_afmoe.py``): sandwich RMSNorms
with plain weights, gated grouped-query attention whose layers are sliding
-window or global by ``layer_types``, a dense SwiGLU in the first
``num_dense_layers`` layers and routed experts behind a sigmoid router with
a selection bias plus one shared expert in the rest, an embedding multiplied
by ``sqrt(hidden_size)`` (``mup_enabled``), an untied head.

*Block.*  ``a = n(x; w_in)``; ``x' = x + n(Attn(a); w_post_attn)``; ``m =
n(x'; w_pre_mlp)``; ``x'' = x' + n(FFN(m); w_post_mlp)`` with ``n(x; w) = x /
rms(x) * w`` (``rms_norm_eps``).

*Attention.*  ``q = n_head(a W_q; w_qn)``, ``k = n_head(a W_k; w_kn)`` (an
RMSNorm over each head's ``head_dim``), ``v = a W_v``, ``g = a W_g``.  In a
``sliding_attention`` layer q and k are rotated (rotate-half over the whole
head, ``rope_theta``, no scaling) and key ``j`` is visible to query ``t``
iff ``t - sliding_window < j <= t``; in a ``full_attention`` layer there is
NO positional embedding and ``j <= t``.  Scores ``q . k * head_dim^-0.5``,
softmax in float32, each KV head serving ``Hq / Hkv`` query heads; ``out =
(concat(o) * sigmoid(g)) W_o``.

*Router.*  ``s = sigmoid(float32(m W_r))`` over all experts; the chosen
experts are the top-k of ``s + b`` (``expert_bias``); their weights are ``s``
at those experts (without ``b``), divided by their sum + 1e-20 when
``route_norm``, times ``route_scale``.  ``n_group`` / ``topk_group`` other
than 1 are refused.  ``FFN = S(m) + sum_k w_k E_k(m)``: ``E`` a SwiGLU of
width ``moe_intermediate_size``, ``S`` one of ``num_shared_experts x`` that,
taken by every token.

*A share.*  ``params`` may hold fewer experts than the router has outputs:
those from ``expert_start`` (``cfg["expert_start"]``).  The router is
unchanged and a token keeps only what the held experts give.

Straightforward float32 ``jax.numpy`` under
``default_matmul_precision("highest")``, layer by layer, no kernels, no
cache, no batching, and no import from ``deepspeed_tpu``.  One sequence at
a time; attention in blocks of query rows against the whole context (the
mask is the inequality above, nothing is skipped); the experts by a plain
loop with a mask, converted to float32 ``expert_block`` at a time (4 of 32:
0.45 GB at the published widths, never the 3.6 GB of a layer's 32).

Parameters are a plain dict the family adapter builds: ``{"embed": [V, H],
"layers": [{"ln_in", "ln_post_attn", "ln_pre_mlp", "ln_post_mlp", "wq",
"wk", "wv", "wg", "wo", "q_norm", "k_norm", then either "gate", "up", "down"
(dense) or "router" [H, E], "bias" [E], "w_gate" [e, H, F], "w_up", "w_down"
[e, F, H], "s_gate", "s_up", "s_down"}, ...], "norm": [H], "lm_head": [H,
V]}``, every matrix stored [in, out].
"""

from __future__ import annotations

import functools
from typing import Dict, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32


def _rms(x, w, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w.astype(F32)


def _silu(x):
    return x * jax.nn.sigmoid(x)


def _rope(x, pos, theta):
    """x: [S, H, D]; rotate-half over the whole of D."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=F32) / d))
    ang = pos.astype(F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(q, k, v, window: Optional[int], q_block: int):
    """q: [S, Hq, D], k, v: [S, Hkv, D] -> [S, Hq*D]; causal, and inside
    the band where ``window`` is given; softmax in float32, one block of
    query rows at a time."""
    s, hq, d = q.shape
    hkv = k.shape[1]
    kpos = jnp.arange(s)
    nblk = -(-s // q_block)
    qp = jnp.pad(q, ((0, nblk * q_block - s), (0, 0), (0, 0)))

    def block(i):
        qs = jax.lax.dynamic_slice_in_dim(qp, i * q_block, q_block, 0)
        qs = qs.reshape(q_block, hkv, hq // hkv, d)
        qpos = i * q_block + jnp.arange(q_block)
        sc = jnp.einsum("qkgd,skd->kgqs", qs, k) * d ** -0.5
        keep = kpos[None, :] <= qpos[:, None]
        if window is not None:
            keep &= kpos[None, :] > qpos[:, None] - window
        sc = jnp.where(keep[None, None], sc, -jnp.inf)
        return jnp.einsum("kgqs,skd->qkgd", jax.nn.softmax(sc, axis=-1), v)

    out = jax.lax.map(block, jnp.arange(nblk))
    return out.reshape(nblk * q_block, hq * d)[:s]


def route(h, router, bias, top_k: int, norm_topk: bool, scale: float):
    """h: [S, H] (the normalised residual) -> (experts [S, k] int32,
    weights [S, k] float32): selection by ``s + bias``, weights from ``s``."""
    s = jax.nn.sigmoid(h.astype(F32) @ router.astype(F32))
    _, idx = jax.lax.top_k(s + bias.astype(F32), top_k)
    w = jnp.take_along_axis(s, idx, axis=-1)
    if norm_topk:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return idx.astype(jnp.int32), w * scale


def routed(h, lp, *, top_k, norm_topk, scale, expert_start):
    """The routed experts' part of the MoE output (held experts only)."""
    idx, w = route(h, lp["router"], lp["bias"], top_k, norm_topk, scale)

    def one(acc, e):                    # e: index among the HELD experts
        y = (_silu(h @ lp["w_gate"][e]) * (h @ lp["w_up"][e])) \
            @ lp["w_down"][e]
        p_e = jnp.sum(jnp.where(idx == e + expert_start, w, 0.0), axis=-1)
        return acc + p_e[:, None] * y, None

    out, _ = jax.lax.scan(one, jnp.zeros_like(h),
                          jnp.arange(lp["w_gate"].shape[0]))
    return out


@functools.partial(jax.jit, static_argnames=(
    "hq", "hkv", "eps", "theta", "window", "rope", "q_block"))
def _attn_layer(x, lp, *, hq, hkv, eps, theta, window, rope, q_block):
    with jax.default_matmul_precision("highest"):
        lp = jax.tree.map(lambda a: a.astype(F32), lp)
        s = x.shape[0]
        a = _rms(x, lp["ln_in"], eps)
        q = _rms((a @ lp["wq"]).reshape(s, hq, -1), lp["q_norm"], eps)
        k = _rms((a @ lp["wk"]).reshape(s, hkv, -1), lp["k_norm"], eps)
        v = (a @ lp["wv"]).reshape(s, hkv, -1)
        if rope:
            pos = jnp.arange(s)
            q, k = _rope(q, pos, theta), _rope(k, pos, theta)
        o = _attention(q, k, v, window, q_block)
        o = (o * jax.nn.sigmoid(a @ lp["wg"])) @ lp["wo"]
        return x + _rms(o, lp["ln_post_attn"], eps)


@functools.partial(jax.jit, static_argnames=("eps",))
def _dense_ffn(x, lp, *, eps):
    with jax.default_matmul_precision("highest"):
        lp = jax.tree.map(lambda a: a.astype(F32), lp)
        m = _rms(x, lp["ln_pre_mlp"], eps)
        y = (_silu(m @ lp["gate"]) * (m @ lp["up"])) @ lp["down"]
        return x + _rms(y, lp["ln_post_mlp"], eps)


@functools.partial(jax.jit, static_argnames=(
    "eps", "top_k", "norm_topk", "scale"))
def _moe_block(x, acc, lp, block, expert_start, *, eps, top_k, norm_topk,
               scale):
    """``acc`` plus what one block of the held experts (``block``: their
    matrices; ``expert_start``: the id of its first) gives."""
    with jax.default_matmul_precision("highest"):
        lp, block = jax.tree.map(lambda a: a.astype(F32), (lp, block))
        m = _rms(x, lp["ln_pre_mlp"], eps)
        return acc + routed(m, {**lp, **block}, top_k=top_k,
                            norm_topk=norm_topk, scale=scale,
                            expert_start=expert_start)


@functools.partial(jax.jit, static_argnames=("eps",))
def _moe_shared(x, acc, lp, *, eps):
    """The shared expert added to the routed sum, the post norm, the
    residual."""
    with jax.default_matmul_precision("highest"):
        lp = jax.tree.map(lambda a: a.astype(F32), lp)
        m = _rms(x, lp["ln_pre_mlp"], eps)
        y = acc + (_silu(m @ lp["s_gate"]) * (m @ lp["s_up"])) @ lp["s_down"]
        return x + _rms(y, lp["ln_post_mlp"], eps)


@functools.partial(jax.jit, static_argnames=("scale",))
def _embed(table, ids, *, scale):
    return table[ids].astype(F32) * scale


@functools.partial(jax.jit, static_argnames=("eps",))
def _logits(x, norm, lm_head, *, eps):
    with jax.default_matmul_precision("highest"):
        return _rms(x, norm, eps) @ lm_head.astype(F32)


def _check(cfg: Dict) -> None:
    if int(cfg.get("n_group", 1)) != 1 or int(cfg.get("topk_group", 1)) != 1:
        raise ValueError("reference/afmoe.py: n_group / topk_group other "
                         "than 1 (group-limited routing) is not implemented")
    if cfg.get("rope_scaling") is not None \
            or cfg.get("score_func", "sigmoid") != "sigmoid" \
            or cfg.get("tie_word_embeddings"):
        raise ValueError("reference/afmoe.py implements the published "
                         "Trinity block: rope_scaling unset, sigmoid "
                         "scoring, an untied head")
    kinds = set(cfg["layer_types"])
    if len(cfg["layer_types"]) != int(cfg["num_hidden_layers"]) \
            or kinds - {"sliding_attention", "full_attention"}:
        raise ValueError(f"reference/afmoe.py: layer_types {kinds}")


_ATTN_KEYS = ("ln_in", "ln_post_attn", "wq", "wk", "wv", "wg", "wo",
              "q_norm", "k_norm")
_EXPERT_KEYS = ("w_gate", "w_up", "w_down")


def hidden(params: Dict, ids: np.ndarray, cfg: Dict, q_block: int = 128,
           expert_block: int = 4) -> jnp.ndarray:
    """The residual stream [S, H] after the last layer of ONE sequence.
    The held experts are converted to float32 ``expert_block`` at a time."""
    _check(cfg)
    s = int(ids.shape[0])
    eps = float(cfg["rms_norm_eps"])
    x = _embed(params["embed"], np.asarray(ids, np.int32),
               scale=float(cfg["hidden_size"]) ** 0.5
               if cfg.get("mup_enabled") else 1.0)
    for kind, lp in zip(cfg["layer_types"], params["layers"]):
        sliding = kind == "sliding_attention"
        x = _attn_layer(
            x, {k: lp[k] for k in _ATTN_KEYS},
            hq=int(cfg["num_attention_heads"]),
            hkv=int(cfg["num_key_value_heads"]), eps=eps,
            theta=float(cfg["rope_theta"]),
            window=int(cfg["sliding_window"]) if sliding else None,
            rope=sliding, q_block=min(q_block, s))
        norms = {k: lp[k] for k in ("ln_pre_mlp", "ln_post_mlp")}
        if "router" not in lp:
            x = _dense_ffn(x, {**norms, **{k: lp[k] for k in (
                "gate", "up", "down")}}, eps=eps)
            continue
        small = {"ln_pre_mlp": lp["ln_pre_mlp"], "router": lp["router"],
                 "bias": lp["bias"]}
        acc = jnp.zeros_like(x)
        held = lp["w_gate"].shape[0]
        start = int(cfg.get("expert_start", 0))
        for e0 in range(0, held, expert_block):
            acc = _moe_block(
                x, acc, small,
                {k: lp[k][e0:e0 + expert_block] for k in _EXPERT_KEYS},
                start + e0, eps=eps, top_k=int(cfg["num_experts_per_tok"]),
                norm_topk=bool(cfg.get("route_norm", True)),
                scale=float(cfg.get("route_scale", 1.0)))
        x = _moe_shared(x, acc, {**norms, **{k: lp[k] for k in (
            "s_gate", "s_up", "s_down")}}, eps=eps)
    return x


def logits_at(params: Dict, ids: np.ndarray, cfg: Dict,
              rows: Sequence[int], q_block: int = 128) -> np.ndarray:
    """Next-token logits [len(rows), vocab] of ONE sequence ``ids`` [S]
    after a full forward pass, at the given positions."""
    x = hidden(params, ids, cfg, q_block)[np.asarray(rows)]
    return np.asarray(_logits(x, params["norm"], params["lm_head"],
                              eps=float(cfg["rms_norm_eps"])), np.float32)
