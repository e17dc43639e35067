"""Plain reference for the GLM-5 block (``model_type: glm_moe_dsa`` as
published by zai-org): the Moonlight block (``reference/moonlight.py``:
pre-norm RMSNorm, multi-head latent attention in its expanded form, a dense
SwiGLU in the leading layers, then a sigmoid router with a selection bias
over routed experts plus a shared expert, untied lm_head) with a low-rank
query and DeepSeek Sparse Attention: a learned indexer that scores every
earlier position and lets a query attend to the best ``index_topk`` alone.

Per token ``t`` at position ``p`` (``x`` the normalised residual):

* ``cq = rmsnorm(x W_qa)`` (its own weight, ``latent_norm_eps``), ``q = cq
  W_qb`` per head ``q_nope | q_pe``; ``[c' | k_pe'] = x W_kva``, ``c =
  rmsnorm(c')``, ``k_pe = rope(k_pe')`` one per token, ``q_pe = rope(q_pe)``;
  ``[k_nope_h | v_h] = c W_kvb``.
* indexer: ``kI = layernorm(x W_Ik)`` (weight and bias, ``index_norm_eps``),
  ``qI = cq W_Iq`` in ``index_n_heads`` heads of ``index_head_dim``; the
  FIRST ``qk_rope_head_dim`` dims of ``kI`` and of each ``qI`` head rotate
  (same angles as the main rope); ``w = (x W_Iw) * HI^-0.5 * DI^-0.5``.
* ``I[t, s] = sum_j w[t, j] relu(qI[t, j] . kI[s])`` for ``s <= p``;
  ``S_t`` = the ``min(index_topk, p + 1)`` positions of the largest ``I[t,
  s]``, ties to the lowest position: a STABLE sort of ``-I`` and its first
  ``index_topk`` entries.
* ``s_h = (q_nope_h . k_nope_h + q_pe_h . k_pe) * (nope + rope)^-0.5`` over
  ``S_t`` only, softmax in float32, ``o_h = sum p v_h``, ``out = concat_h(o_h)
  W_o``.

Departures from the published inference code, each an ``assumed`` entry of
the configuration file: no Hadamard rotation of ``qI`` / ``kI`` (orthogonal:
``qI . kI`` is unchanged) and no FP8 quantisation of them; rotate-half rope
layout (the published ``rope_interleave`` / ``indexer_rope_interleave`` true
is a loader's permutation); the multi-token-prediction layer is not part of
the path to the main head's logits and is refused.

``I`` is a ``[q_block, S]`` causal matrix a block of query rows at a time,
the whole sequence at once: no cache, no batching, no import from
``deepspeed_tpu``.  The FFN blocks, embedding and head are Moonlight's.

Parameters: Moonlight's dict with ``"wqa" [H, qr]``, ``"q_norm" [qr]``,
``"wqb" [qr, Hq*(nope+rope)]`` in place of ``"wq"``, and ``"wiq" [qr,
HI*DI]``, ``"wik" [H, DI]``, ``"ik_norm_w"``, ``"ik_norm_b" [DI]``, ``"wiw"
[H, HI]``.
"""

from __future__ import annotations

import functools
from typing import Dict, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference import moonlight as base
from benchmark.reference.moonlight import F32, _rms, _rope

_ATTN_KEYS = ("ln1", "wqa", "q_norm", "wqb", "wkva", "kv_norm", "wkvb", "wo",
              "wiq", "wik", "ik_norm_w", "ik_norm_b", "wiw")


def _layer_norm(x, w, b, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * w + b


def _rope_first(x, pos, theta, rope):
    """x: [S, H, D]; the first ``rope`` dims rotate, the rest pass."""
    return jnp.concatenate([_rope(x[..., :rope], pos, theta), x[..., rope:]],
                           -1)


def index_matrix(qi, ki, w, q0):
    """``I`` for the query rows from ``q0``: qi [Q, HI, DI], ki [S, DI], w
    [Q, HI] -> [Q, S], ``-inf`` past each row's own position."""
    s = jnp.einsum("qjd,sd->qjs", qi, ki)
    s = jnp.einsum("qjs,qj->qs", jax.nn.relu(s), w)
    qpos = q0 + jnp.arange(qi.shape[0])
    return jnp.where(jnp.arange(ki.shape[0])[None, :] <= qpos[:, None], s,
                     -jnp.inf)


def selection(scores, topk: int):
    """[Q, S] scores -> the boolean mask of each row's ``topk`` largest
    finite ones, ties to the lowest position (a stable sort)."""
    order = jnp.argsort(-scores, axis=-1, stable=True)
    rank = jnp.argsort(order, axis=-1, stable=True)
    return (rank < topk) & jnp.isfinite(scores)


def _dsa(h, lp, *, hq, rank, nope, rope, vd, hi, di, topk, theta,
         latent_eps, index_eps, q_block):
    s = h.shape[0]
    pos = jnp.arange(s)
    cq = _rms(h @ lp["wqa"], lp["q_norm"], latent_eps)
    q = (cq @ lp["wqb"]).reshape(s, hq, nope + rope)
    kva = h @ lp["wkva"]
    c = _rms(kva[:, :rank], lp["kv_norm"], latent_eps)
    k_pe = _rope(kva[:, None, rank:], pos, theta)             # [S, 1, rope]
    kv = (c @ lp["wkvb"]).reshape(s, hq, nope + vd)
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_pe, (s, hq, rope))], -1)
    v = kv[..., nope:]
    qf = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], pos, theta)],
                         -1)
    ki = _rope_first(_layer_norm(h @ lp["wik"], lp["ik_norm_w"],
                                 lp["ik_norm_b"], index_eps)[:, None],
                     pos, theta, rope)[:, 0]
    qi = _rope_first((cq @ lp["wiq"]).reshape(s, hi, di), pos, theta, rope)
    w = (h @ lp["wiw"]) * (hi ** -0.5 * di ** -0.5)
    scale = (nope + rope) ** -0.5
    nblk = -(-s // q_block)
    pad = lambda a: jnp.pad(a, ((0, nblk * q_block - s),)
                            + ((0, 0),) * (a.ndim - 1))
    qf, qi, w = pad(qf), pad(qi), pad(w)

    def block(i):
        cut = lambda a: jax.lax.dynamic_slice_in_dim(a, i * q_block, q_block,
                                                     0)
        sel = selection(index_matrix(cut(qi), ki, cut(w), i * q_block), topk)
        sc = jnp.einsum("qhd,shd->hqs", cut(qf), k) * scale
        sc = jnp.where(sel[None], sc, -jnp.inf)
        # (a pad row past the sequence's end selects position 0 .. itself
        # too: every row has a finite score)
        return jnp.einsum("hqs,shd->qhd", jax.nn.softmax(sc, axis=-1), v)

    out = jax.lax.map(block, jnp.arange(nblk))
    return out.reshape(nblk * q_block, -1)[:s] @ lp["wo"]


@functools.partial(jax.jit, static_argnames=(
    "hq", "rank", "nope", "rope", "vd", "hi", "di", "topk", "eps",
    "latent_eps", "index_eps", "theta", "q_block"))
def _attn_layer(x, lp, *, eps, **kw):
    with jax.default_matmul_precision("highest"):
        lp = jax.tree.map(lambda a: a.astype(F32), lp)
        return x + _dsa(_rms(x, lp["ln1"], eps), lp, **kw)


def _check(cfg: Dict) -> None:
    if cfg.get("index_topk") is None or cfg.get("q_lora_rank") is None:
        raise ValueError("reference/glm_moe_dsa.py implements the published "
                         "GLM-5 block: q_lora_rank and index_topk set")
    if cfg.get("rope_scaling") is not None or (
            cfg.get("rope_parameters") or {}).get(
                "rope_type", "default") != "default":
        raise ValueError("reference/glm_moe_dsa.py: plain rope only")
    # the FFN blocks, the router's refusals and the MTP refusal are
    # Moonlight's own
    base._check({**cfg, "q_lora_rank": None})


def hidden(params: Dict, ids: np.ndarray, cfg: Dict, q_block: int = 256,
           expert_block: int = 4) -> jnp.ndarray:
    """The residual stream [S, H] after the last layer of ONE sequence."""
    _check(cfg)
    s = int(ids.shape[0])
    eps = float(cfg["rms_norm_eps"])
    x = base._embed(params["embed"], np.asarray(ids, np.int32))
    for lp in params["layers"]:
        x = _attn_layer(
            x, {k: lp[k] for k in _ATTN_KEYS},
            hq=int(cfg["num_attention_heads"]),
            rank=int(cfg["kv_lora_rank"]),
            nope=int(cfg["qk_nope_head_dim"]),
            rope=int(cfg["qk_rope_head_dim"]), vd=int(cfg["v_head_dim"]),
            hi=int(cfg["index_n_heads"]), di=int(cfg["index_head_dim"]),
            topk=int(cfg["index_topk"]), eps=eps,
            latent_eps=float(cfg.get("latent_norm_eps", 1e-6)),
            index_eps=float(cfg.get("index_norm_eps", 1e-6)),
            theta=float(rope_theta(cfg)), q_block=min(q_block, s))
        x = _ffn(x, lp, cfg, eps, expert_block)
    return x


def _ffn(x, lp, cfg: Dict, eps: float, expert_block: int):
    """Moonlight's FFN blocks: a dense SwiGLU, or the held experts
    (converted to float32 ``expert_block`` at a time) plus the shared one."""
    if "router" not in lp:
        return base._dense_ffn(x, {k: lp[k] for k in ("ln2", "gate", "up",
                                                      "down")}, eps=eps)
    small = {k: lp[k] for k in ("ln2", "router", "bias")}
    acc = jnp.zeros_like(x)
    start = int(cfg.get("expert_start", 0))
    for e0 in range(0, lp["w_gate"].shape[0], expert_block):
        acc = base._moe_block(
            x, acc, small,
            {k: lp[k][e0:e0 + expert_block] for k in base._EXPERT_KEYS},
            start + e0, eps=eps, top_k=int(cfg["num_experts_per_tok"]),
            norm_topk=bool(cfg.get("norm_topk_prob", True)),
            scale=float(cfg.get("routed_scaling_factor", 1.0)))
    return base._moe_shared(x, acc, {k: lp[k] for k in (
        "ln2", "s_gate", "s_up", "s_down")}, eps=eps)


def rope_theta(cfg: Dict) -> float:
    """``rope_theta`` where the configuration carries it at the top level,
    else inside ``rope_parameters`` (as GLM-5's does)."""
    if "rope_theta" in cfg:
        return float(cfg["rope_theta"])
    return float(cfg["rope_parameters"]["rope_theta"])


def logits_at(params: Dict, ids: np.ndarray, cfg: Dict,
              rows: Sequence[int], q_block: int = 256) -> np.ndarray:
    """Next-token logits [len(rows), vocab] of ONE sequence ``ids`` [S]
    after a full forward pass, at the given positions."""
    x = hidden(params, ids, cfg, q_block)[np.asarray(rows)]
    return np.asarray(base._logits(x, params["norm"], params["lm_head"],
                                   eps=float(cfg["rms_norm_eps"])),
                      np.float32)
