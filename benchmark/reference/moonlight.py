"""Plain reference for the Moonlight-16B-A3B block (``model_type:
deepseek_v3`` as published by moonshotai): pre-norm RMSNorm with a plain
weight; multi-head latent attention in its **expanded** form only; layer 0
a dense SwiGLU, every later layer routed experts behind a sigmoid router
with a selection bias, plus shared experts; untied lm_head.

*Latent attention.*  ``q = x W_q`` (per head ``q_nope | q_pe``);
``[c' | k_pe'] = x W_kva``; ``c = rmsnorm(c')`` with its own weight and eps
(``latent_norm_eps``, the published code's default 1e-6); ``k_pe =
rope(k_pe')``, ONE per token for all heads; ``q_pe = rope(q_pe)`` per head;
rotary over the rope dims in the rotate-half form (the parameters here are
already de-interleaved).  ``[k_nope_h | v_h] = c W_kvb``; ``s_h = (q_nope_h .
k_nope_h + q_pe_h . k_pe) * (nope + rope)^-0.5``; causal softmax in float32;
``o_h = sum p v_h``; ``out = concat_h(o_h) W_o``.  No cache, no absorbed
form: what the served program's two paths are both compared with.

*Router.*  ``s = sigmoid(x W_g)`` over all experts; the chosen experts are
the top-k of ``s + b``; their weights are ``s`` at those experts (without
``b``), divided by their sum + 1e-20 when ``norm_topk_prob``, times
``routed_scaling_factor``.  ``n_group`` / ``topk_group`` other than 1 are
refused.  ``y = sum_k w_k E_k(x) + S(x)``: ``E`` a SwiGLU of width
``moe_intermediate_size``, ``S`` one SwiGLU of width ``n_shared_experts x``
that, without a gate, taken by every token.

*A share.*  ``params`` may hold fewer experts than the router has outputs:
those from ``expert_start`` (``cfg["expert_start"]``).  The router is
unchanged and a token keeps only what the held experts give.

Straightforward float32 ``jax.numpy`` under
``default_matmul_precision("highest")``, layer by layer, no kernels, no
cache, no batching, and no import from ``deepspeed_tpu``.  One sequence at
a time; attention in blocks of query rows against the whole context; the
experts by a plain loop with a mask, ``expert_block`` at a time.

Parameters are a plain dict the family adapter builds:
``{"embed": [V, H], "layers": [{"ln1", "ln2", "wq" [H, Hq*(nope+rope)],
"wkva" [H, rank+rope], "kv_norm" [rank], "wkvb" [rank, Hq*(nope+v)], "wo"
[Hq*v, H], then either "gate", "up", "down" (dense) or "router" [H, E],
"bias" [E], "w_gate" [e, H, F], "w_up", "w_down" [e, F, H], "s_gate",
"s_up", "s_down"}, ...], "norm": [H], "lm_head": [H, V]}``, every matrix
stored [in, out].
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


def _rope(x, pos, theta):
    """x: [S, H, D]; rotate-half over the whole of D."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=F32) / d))
    ang = pos.astype(F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(q, k, v, scale, q_block):
    """q, k: [S, H, Dqk], v: [S, H, Dv] -> [S, H*Dv]; causal, softmax in
    float32, one block of query rows at a time."""
    s, h, _ = q.shape
    kpos = jnp.arange(s)
    nblk = -(-s // q_block)
    qp = jnp.pad(q, ((0, nblk * q_block - s), (0, 0), (0, 0)))

    def block(i):
        qs = jax.lax.dynamic_slice_in_dim(qp, i * q_block, q_block, 0)
        qpos = i * q_block + jnp.arange(q_block)
        sc = jnp.einsum("qhd,shd->hqs", qs, k) * scale
        keep = kpos[None, :] <= qpos[:, None]
        sc = jnp.where(keep[None], sc, -jnp.inf)
        return jnp.einsum("hqs,shd->qhd", jax.nn.softmax(sc, axis=-1), v)

    out = jax.lax.map(block, jnp.arange(nblk))
    return out.reshape(nblk * q_block, -1)[:s]


def _mla(h, lp, *, hq, rank, nope, rope, vd, theta, latent_eps, q_block):
    s = h.shape[0]
    pos = jnp.arange(s)
    q = (h @ lp["wq"]).reshape(s, hq, nope + rope)
    kva = h @ lp["wkva"]
    c = _rms(kva[:, :rank], lp["kv_norm"], latent_eps)
    k_pe = _rope(kva[:, None, rank:], pos, theta)             # [S, 1, rope]
    q_pe = _rope(q[..., nope:], pos, theta)
    kv = (c @ lp["wkvb"]).reshape(s, hq, nope + vd)           # expanded
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_pe, (s, hq, rope))], -1)
    qf = jnp.concatenate([q[..., :nope], q_pe], -1)
    return _attention(qf, k, kv[..., nope:], (nope + rope) ** -0.5,
                      q_block) @ lp["wo"]


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


def shared(h, lp):
    """The shared experts: one ungated SwiGLU every token takes."""
    return (_silu(h @ lp["s_gate"]) * (h @ lp["s_up"])) @ lp["s_down"]


@functools.partial(jax.jit, static_argnames=(
    "hq", "rank", "nope", "rope", "vd", "eps", "latent_eps", "theta",
    "q_block"))
def _attn_layer(x, lp, *, hq, rank, nope, rope, vd, eps, latent_eps, theta,
                q_block):
    with jax.default_matmul_precision("highest"):
        lp = jax.tree.map(lambda a: a.astype(F32), lp)
        return x + _mla(_rms(x, lp["ln1"], eps), lp, hq=hq, rank=rank,
                        nope=nope, rope=rope, vd=vd, theta=theta,
                        latent_eps=latent_eps, q_block=q_block)


@functools.partial(jax.jit, static_argnames=("eps",))
def _dense_ffn(x, lp, *, eps):
    with jax.default_matmul_precision("highest"):
        lp = jax.tree.map(lambda a: a.astype(F32), lp)
        h = _rms(x, lp["ln2"], eps)
        return x + (_silu(h @ lp["gate"]) * (h @ lp["up"])) @ lp["down"]


@functools.partial(jax.jit, static_argnames=(
    "eps", "top_k", "norm_topk", "scale"))
def _moe_block(x, acc, lp, block, expert_start, *, eps, top_k, norm_topk,
               scale):
    """``acc`` plus what one block of the held experts (``block``: their
    matrices; ``expert_start``: the id of its first) gives."""
    with jax.default_matmul_precision("highest"):
        lp, block = jax.tree.map(lambda a: a.astype(F32), (lp, block))
        h = _rms(x, lp["ln2"], eps)
        return acc + routed(h, {**lp, **block}, top_k=top_k,
                            norm_topk=norm_topk, scale=scale,
                            expert_start=expert_start)


@functools.partial(jax.jit, static_argnames=("eps",))
def _moe_shared(x, acc, lp, *, eps):
    with jax.default_matmul_precision("highest"):
        lp = jax.tree.map(lambda a: a.astype(F32), lp)
        return x + acc + shared(_rms(x, lp["ln2"], eps), lp)


@jax.jit
def _embed(table, ids):
    return table[ids].astype(F32)


@functools.partial(jax.jit, static_argnames=("eps",))
def _logits(x, norm, lm_head, *, eps):
    with jax.default_matmul_precision("highest"):
        return _rms(x, norm, eps) @ lm_head.astype(F32)


def _check(cfg: Dict) -> None:
    if int(cfg.get("n_group", 1)) != 1 or int(cfg.get("topk_group", 1)) != 1:
        raise ValueError("reference/moonlight.py: n_group / topk_group other "
                         "than 1 (group-limited routing) is not implemented")
    if cfg.get("attention_bias") or cfg.get("rope_scaling") is not None \
            or cfg.get("q_lora_rank") is not None \
            or cfg.get("scoring_func", "sigmoid") != "sigmoid" \
            or int(cfg.get("moe_layer_freq", 1)) != 1 \
            or int(cfg.get("num_nextn_predict_layers", 0)) != 0:
        raise ValueError("reference/moonlight.py implements the published "
                         "Moonlight block: attention_bias, rope_scaling, "
                         "q_lora_rank unset; sigmoid scoring; every layer "
                         "past the dense ones routed; no MTP module")


_EXPERT_KEYS = ("w_gate", "w_up", "w_down")


def hidden(params: Dict, ids: np.ndarray, cfg: Dict, q_block: int = 512,
           expert_block: int = 4) -> jnp.ndarray:
    """The residual stream [S, H] after the last layer of ONE sequence.
    The held experts are converted to float32 ``expert_block`` at a time."""
    _check(cfg)
    s = int(ids.shape[0])
    eps = float(cfg["rms_norm_eps"])
    x = _embed(params["embed"], np.asarray(ids, np.int32))
    for lp in params["layers"]:
        x = _attn_layer(
            x, {k: lp[k] for k in ("ln1", "wq", "wkva", "kv_norm", "wkvb",
                                   "wo")},
            hq=int(cfg["num_attention_heads"]),
            rank=int(cfg["kv_lora_rank"]),
            nope=int(cfg["qk_nope_head_dim"]),
            rope=int(cfg["qk_rope_head_dim"]), vd=int(cfg["v_head_dim"]),
            eps=eps, latent_eps=float(cfg.get("latent_norm_eps", 1e-6)),
            theta=float(cfg["rope_theta"]), q_block=min(q_block, s))
        if "router" not in lp:
            x = _dense_ffn(x, {k: lp[k] for k in ("ln2", "gate", "up",
                                                  "down")}, eps=eps)
            continue
        small = {k: lp[k] for k in ("ln2", "router", "bias")}
        acc = jnp.zeros_like(x)
        held = lp["w_gate"].shape[0]
        start = int(cfg.get("expert_start", 0))
        for e0 in range(0, held, expert_block):
            acc = _moe_block(
                x, acc, small,
                {k: lp[k][e0:e0 + expert_block] for k in _EXPERT_KEYS},
                start + e0, eps=eps, top_k=int(cfg["num_experts_per_tok"]),
                norm_topk=bool(cfg.get("norm_topk_prob", True)),
                scale=float(cfg.get("routed_scaling_factor", 1.0)))
        x = _moe_shared(x, acc, {k: lp[k] for k in ("ln2", "s_gate", "s_up",
                                                    "s_down")}, eps=eps)
    return x


def logits_at(params: Dict, ids: np.ndarray, cfg: Dict,
              rows: Sequence[int], q_block: int = 512) -> np.ndarray:
    """Next-token logits [len(rows), vocab] of ONE sequence ``ids`` [S]
    after a full forward pass, at the given positions."""
    x = hidden(params, ids, cfg, q_block)[np.asarray(rows)]
    return np.asarray(_logits(x, params["norm"], params["lm_head"],
                              eps=float(cfg["rms_norm_eps"])), np.float32)
