"""Plain reference for the LongCat-Flash block (``model_type:
longcat_flash``; meituan-longcat/LongCat-Flash-Omni's language model): a
published layer is TWO sub-blocks, each latent attention then a dense
SwiGLU, with ONE routed branch that leaves the residual stream after the
first sub-block's attention and rejoins it after the second sub-block's
dense FFN (shortcut-connected MoE); untied lm_head.

*One layer*, on the residual stream ``h``, for ``j`` in (0, 1)::

    a   = rmsnorm(h; ln1[j])
    h   = h + MLA_j(a)
    m_j = rmsnorm(h; ln2[j])
    y   = MoE(m_0)                       (j = 0 only; kept aside)
    h   = h + down_j(silu(gate_j m_j) * up_j m_j)
    h   = h + y                          (after j = 1)

*Latent attention* (the expanded form only, no cache): ``cq = rmsnorm(a
W_qa)``; ``q = s_q (cq W_qb)`` per head ``q_nope | q_pe``, ``s_q =
sqrt(hidden / q_lora_rank)``; ``[c' | k_pe'] = a W_kva``; ``c = s_kv
rmsnorm(c')``, ``s_kv = sqrt(hidden / kv_lora_rank)``; ``k_pe = rope(k_pe')``
(ONE per token, NOT scaled); ``q_pe = rope(q_pe)``; ``[k_nope_h | v_h] = c
W_kvb``; scores ``(q_nope . k_nope + q_pe . k_pe) (nope + rope)^-0.5``,
causal softmax in float32, ``o = sum p v``, ``out = concat_h(o_h) W_o``.  The
two latent norms have their own eps (``latent_norm_eps``, the published
modelling code's default 1e-6).

*The routed branch.*  ``z = m W_r`` over ``n_routed_experts +
zero_expert_num`` outputs (no bias term in the linear); ``s = softmax(z)``
over ALL of them; the chosen outputs are the top ``moe_topk`` of ``s + b``
(``b`` the selection bias; ties to the lowest index); ``w_i =
routed_scaling_factor x s_i`` at the chosen, without ``b`` and without
renormalisation; ``y = sum_{chosen i < E} w_i E_i(m) + (sum_{chosen i >= E}
w_i) m``: an expert is a SwiGLU of width ``expert_ffn_hidden_size``, a zero
-compute expert the identity.

*A share.*  ``params`` may hold fewer experts than the router has: those
from ``cfg["expert_start"]``.  The router is unchanged, a token keeps what
the held experts give, and the zero term is kept in full (it belongs to the
token's own chip).  ``zero_term=False`` leaves it out: the share test adds
the shares of all chips and counts that term once.

Departures from ``config.json`` (each followed from the published modelling
code of the family): the latent norms' eps, where the two scale factors are
applied, ``norm_topk_prob`` false, no bias in the router's linear, the
untied head; rotary in the rotate-half form (the parameters here are
already de-interleaved).  Left out: the multi-token-prediction head and the
audio / vision encoders (not the language model's path to its logits).

Straightforward float32 ``jax.numpy`` under
``default_matmul_precision("highest")``, layer by layer, no kernels, no
cache, no batching, and no import from ``deepspeed_tpu``.

Parameters are a plain dict the family adapter builds: ``{"embed": [V, H],
"layers": [{"subs": [{"ln1", "ln2", "wqa" [H, qr], "q_norm" [qr], "wqb"
[qr, Hq*(nope+rope)], "wkva" [H, rank+rope], "kv_norm" [rank], "wkvb"
[rank, Hq*(nope+v)], "wo" [Hq*v, H], "gate", "up", "down"}, x 2],
"router" [H, E+Z], "bias" [E+Z], "w_gate" [e, H, F], "w_up", "w_down" [e,
F, H]}, ...], "norm": [H], "lm_head": [H, V]}``, every matrix [in, out].
"""

from __future__ import annotations

import functools
from typing import Dict, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference.moonlight import (F32, _attention, _embed, _logits,
                                           _rms, _rope, _silu)

_ATTN_KEYS = ("ln1", "wqa", "q_norm", "wqb", "wkva", "kv_norm", "wkvb", "wo")
_FFN_KEYS = ("ln2", "gate", "up", "down")
_EXPERT_KEYS = ("w_gate", "w_up", "w_down")


def _mla(a, lp, *, hq, rank, nope, rope, vd, theta, latent_eps, s_q, s_kv,
         q_block):
    s = a.shape[0]
    pos = jnp.arange(s)
    cq = _rms(a @ lp["wqa"], lp["q_norm"], latent_eps)
    q = s_q * (cq @ lp["wqb"]).reshape(s, hq, nope + rope)
    kva = a @ lp["wkva"]
    c = s_kv * _rms(kva[:, :rank], lp["kv_norm"], latent_eps)
    k_pe = _rope(kva[:, None, rank:], pos, theta)             # [S, 1, rope]
    q_pe = _rope(q[..., nope:], pos, theta)
    kv = (c @ lp["wkvb"]).reshape(s, hq, nope + vd)
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_pe, (s, hq, rope))], -1)
    qf = jnp.concatenate([q[..., :nope], q_pe], -1)
    return _attention(qf, k, kv[..., nope:], (nope + rope) ** -0.5,
                      q_block) @ lp["wo"]


def route(m, router, bias, top_k: int, scale: float):
    """m: [S, H] -> (outputs [S, k] int32, weights [S, k] float32):
    softmax over every output, selection by ``s + bias``, weights ``scale x
    s`` (no renormalisation)."""
    s = jax.nn.softmax(m.astype(F32) @ router.astype(F32), axis=-1)
    _, idx = jax.lax.top_k(s + bias.astype(F32), top_k)
    return idx.astype(jnp.int32), jnp.take_along_axis(s, idx, -1) * scale


def experts_part(m, lp, idx, w, expert_start):
    """What the held experts (``lp``'s matrices, ids from
    ``expert_start``) give the routed sum."""
    def one(acc, e):
        y = (_silu(m @ lp["w_gate"][e]) * (m @ lp["w_up"][e])) \
            @ lp["w_down"][e]
        p_e = jnp.sum(jnp.where(idx == e + expert_start, w, 0.0), axis=-1)
        return acc + p_e[:, None] * y, None

    out, _ = jax.lax.scan(one, jnp.zeros_like(m),
                          jnp.arange(lp["w_gate"].shape[0]))
    return out


def zero_part(m, idx, w, n_experts: int):
    """The zero-compute (identity) experts' term: ``(sum of the chosen
    zero outputs' weights) m``."""
    return jnp.sum(jnp.where(idx >= n_experts, w, 0.0), -1)[:, None] * m


@functools.partial(jax.jit, static_argnames=(
    "hq", "rank", "nope", "rope", "vd", "eps", "latent_eps", "theta", "s_q",
    "s_kv", "q_block"))
def _attn_sub(x, lp, *, eps, **kw):
    """``h + MLA(rmsnorm(h))`` of one sub-block."""
    with jax.default_matmul_precision("highest"):
        lp = jax.tree.map(lambda a: a.astype(F32), lp)
        return x + _mla(_rms(x, lp["ln1"], eps), lp, **kw)


@functools.partial(jax.jit, static_argnames=("eps",))
def _dense_sub(x, lp, *, eps):
    with jax.default_matmul_precision("highest"):
        lp = jax.tree.map(lambda a: a.astype(F32), lp)
        m = _rms(x, lp["ln2"], eps)
        return x + (_silu(m @ lp["gate"]) * (m @ lp["up"])) @ lp["down"]


@functools.partial(jax.jit, static_argnames=(
    "eps", "top_k", "scale", "n_experts", "zero_term"))
def _branch_start(x, ln2, router, bias, *, eps, top_k, scale, n_experts,
                  zero_term):
    """The branch's input ``m_0``, its routing, and the zero term."""
    with jax.default_matmul_precision("highest"):
        m = _rms(x, ln2.astype(F32), eps)
        idx, w = route(m, router, bias, top_k, scale)
        y = zero_part(m, idx, w, n_experts) if zero_term \
            else jnp.zeros_like(m)
        return m, idx, w, y


@jax.jit
def _branch_block(m, idx, w, acc, block, expert_start):
    """``acc`` plus what one block of the held experts gives."""
    with jax.default_matmul_precision("highest"):
        block = jax.tree.map(lambda a: a.astype(F32), block)
        return acc + experts_part(m, block, idx, w, expert_start)


def _check(cfg: Dict) -> None:
    if cfg.get("zero_expert_type", "identity") != "identity" \
            or cfg.get("attention_bias") \
            or cfg.get("rope_scaling") is not None:
        raise ValueError("reference/longcat_flash.py implements the "
                         "published block: identity zero-compute experts, "
                         "no attention bias, plain rope")


def scales(cfg: Dict):
    """(s_q, s_kv): what ``mla_scale_q_lora`` / ``mla_scale_kv_lora``
    multiply the query and the normalised latent by."""
    h = float(cfg["hidden_size"])
    return ((h / cfg["q_lora_rank"]) ** 0.5
            if cfg.get("mla_scale_q_lora") else 1.0,
            (h / cfg["kv_lora_rank"]) ** 0.5
            if cfg.get("mla_scale_kv_lora") else 1.0)


def hidden(params: Dict, ids: np.ndarray, cfg: Dict, q_block: int = 512,
           expert_block: int = 4, zero_term: bool = True) -> jnp.ndarray:
    """The residual stream [S, H] after the last layer of ONE sequence.
    The held experts are converted to float32 ``expert_block`` at a time."""
    _check(cfg)
    s = int(ids.shape[0])
    eps = float(cfg["rms_norm_eps"])
    s_q, s_kv = scales(cfg)
    attn = dict(
        hq=int(cfg["num_attention_heads"]), rank=int(cfg["kv_lora_rank"]),
        nope=int(cfg["qk_nope_head_dim"]), rope=int(cfg["qk_rope_head_dim"]),
        vd=int(cfg["v_head_dim"]), eps=eps,
        latent_eps=float(cfg.get("latent_norm_eps", 1e-6)),
        theta=float(cfg["rope_theta"]), s_q=s_q, s_kv=s_kv,
        q_block=min(q_block, s))
    start = int(cfg.get("expert_start", 0))
    x = _embed(params["embed"], np.asarray(ids, np.int32))
    for lp in params["layers"]:
        n_experts = lp["router"].shape[1] - int(cfg["zero_expert_num"])
        for j, sp in enumerate(lp["subs"]):
            x = _attn_sub(x, {k: sp[k] for k in _ATTN_KEYS}, **attn)
            if j == 0:
                m, idx, w, y = _branch_start(
                    x, sp["ln2"], lp["router"], lp["bias"], eps=eps,
                    top_k=int(cfg["moe_topk"]),
                    scale=float(cfg["routed_scaling_factor"]),
                    n_experts=n_experts, zero_term=zero_term)
                for e0 in range(0, lp["w_gate"].shape[0], expert_block):
                    y = _branch_block(
                        m, idx, w, y,
                        {k: lp[k][e0:e0 + expert_block]
                         for k in _EXPERT_KEYS}, start + e0)
            x = _dense_sub(x, {k: sp[k] for k in _FFN_KEYS}, eps=eps)
        x = x + y
    return x


def logits_at(params: Dict, ids: np.ndarray, cfg: Dict,
              rows: Sequence[int], q_block: int = 512) -> np.ndarray:
    """Next-token logits [len(rows), vocab] of ONE sequence ``ids`` [S]
    after a full forward pass, at the given positions."""
    x = hidden(params, ids, cfg, q_block)[np.asarray(rows)]
    return np.asarray(_logits(x, params["norm"], params["lm_head"],
                              eps=float(cfg["rms_norm_eps"])), np.float32)
