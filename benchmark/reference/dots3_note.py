"""Plain reference for the dots3-note block (``model_type: dots3_note`` as
published by dots-studio; the language model of dots3-note-prev): pre-norm
RMSNorm, multi-head latent attention of TWO geometries mixed by
``layer_types``, a gate a head, a dense SwiGLU in the leading layer, then a
sigmoid router with a selection bias over routed experts plus one shared
expert, untied lm_head.  With ``x_n = rmsnorm(x)``: ``x += Attn(x_n)``, ``x
+= FFN(rmsnorm(x))``.

Per token ``t`` at position ``p``, on BOTH kinds of layer with that kind's
widths (``H`` heads, ranks ``r_q`` / ``r_kv``, ``nope``, ``rope``, ``v``, rotary
base ``theta``):

* ``c_q = s_q rmsnorm(x_n W_qa)`` (its own weight, ``latent_norm_eps``),
  ``q_h = c_q W_qb[h]`` = ``q_nope | q_pe``, ``q_pe`` rotated;
  ``[c' | k_pe'] = x_n W_kva``, ``c = s_kv rmsnorm(c')``, ``k_pe =
  rope(k_pe')`` one per token; ``k_h(j) = [c_j W_uk[h] | k_pe_j]``, ``v_h(j)
  = c_j W_uv[h]`` (``W_kvb``'s columns per head ``k_nope | v``).
* ``s_q = sqrt(hidden / r_q)``, ``s_kv = sqrt(hidden / r_kv)`` when
  ``apply_mla_qkv_lora_rescale`` (read as LongCat-Flash's
  ``mla_scale_q_lora`` / ``mla_scale_kv_lora``), else 1.
* ``p = softmax_j(q_h . k_h(j) (nope + rope)^-0.5)`` in float32 over the
  positions the layer's kind lets ``t`` see; ``o_h = sum_j p_j v_h(j)``.
* ``g = sigmoid(x_n W_g)`` in R^H (``attention_gate_type: headwise``);
  ``Attn = concat_h(g_h o_h) W_o``.

**A sliding layer** (``sliding_attention``; the ``swa_*`` keys) sees ``p -
sliding_window_size < j <= p``: a mask, nothing else.  **A full layer**
(``full_attention``) sees the exact top ``index_topk`` of ``j <= p`` by the
indexer of ``reference/glm_moe_dsa.py`` (``index_matrix`` / ``selection``:
``kI = layernorm(x_n W_Ik)``, ``qI`` from the UNSCALED ``rmsnorm(x_n W_qa)``
(``s_q`` is one positive factor on every score of a row and changes no
order), the first ``rope`` dims of both rotated by the full layers' base,
``w = x_n W_Iw HI^-0.5 DI^-0.5``; ties to the lowest position): a mask too.
Keys and values are EXPANDED on both kinds; there is no cache, no absorbed
form, no band of blocks.

Departures from the published description, each an ``assumed`` entry of
the configuration file: the two keys above read by the repository's
conventions; no Hadamard rotation or FP8 quantisation of the indexer;
rotate-half rope layout; the vision tower, the audio encoder and
multi-token prediction are not on the path from token ids to the main
head's logits and are left out.

A share, as Moonlight's reference: ``params`` may hold fewer experts than
the router has outputs (those from ``cfg["expert_start"]``) and a slice of
the vocabulary.  Straightforward float32 ``jax.numpy`` under
``default_matmul_precision("highest")``, one sequence at a time, attention
in blocks of ``q_block`` query rows against the whole context (so that a
6,144-token check fits the chip), no import from ``deepspeed_tpu``.  The
FFN blocks, embedding and head are Moonlight's.

Parameters: Moonlight's dict; a layer holds ``"wqa" [H, r_q]``, ``"q_norm"``,
``"wqb" [r_q, Hq*(nope+rope)]``, ``"wkva"``, ``"kv_norm"``, ``"wkvb"``, ``"wg"
[H, Hq]``, ``"wo"`` at its kind's widths, and a full layer also ``"wiq"``,
``"wik"``, ``"ik_norm_w"``, ``"ik_norm_b"``, ``"wiw"``.
"""

from __future__ import annotations

import functools
from typing import Dict, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference import moonlight as base
from benchmark.reference.glm_moe_dsa import (_ffn, _layer_norm, _rope_first,
                                             index_matrix, selection)
from benchmark.reference.moonlight import F32, _rms, _rope

_ATTN_KEYS = ("ln1", "wqa", "q_norm", "wqb", "wkva", "kv_norm", "wkvb", "wg",
              "wo")
_INDEX_KEYS = ("wiq", "wik", "ik_norm_w", "ik_norm_b", "wiw")
KINDS = ("sliding_attention", "full_attention")


def _latent_attention(h, lp, *, hq, rank, nope, rope, vd, theta, s_q, s_kv,
                      latent_eps, window, hi, di, topk, index_eps, q_block):
    """One attention branch over the normed residual ``h`` [S, hidden];
    ``window`` set: a sliding layer, else ``topk`` (a full layer)."""
    s = h.shape[0]
    pos = jnp.arange(s)
    cq = _rms(h @ lp["wqa"], lp["q_norm"], latent_eps)
    q = ((s_q * cq) @ lp["wqb"]).reshape(s, hq, nope + rope)
    kva = h @ lp["wkva"]
    c = s_kv * _rms(kva[:, :rank], lp["kv_norm"], latent_eps)
    k_pe = _rope(kva[:, None, rank:], pos, theta)             # [S, 1, rope]
    kv = (c @ lp["wkvb"]).reshape(s, hq, nope + vd)
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_pe, (s, hq, rope))], -1)
    v = kv[..., nope:]
    qf = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], pos, theta)],
                         -1)
    gate = jax.nn.sigmoid(h @ lp["wg"])                       # [S, Hq]
    scale = (nope + rope) ** -0.5
    nblk = -(-s // q_block)
    pad = lambda a: jnp.pad(a, ((0, nblk * q_block - s),)
                            + ((0, 0),) * (a.ndim - 1))
    qf = pad(qf)
    if window is None:
        ki = _rope_first(_layer_norm(h @ lp["wik"], lp["ik_norm_w"],
                                     lp["ik_norm_b"], index_eps)[:, None],
                         pos, theta, rope)[:, 0]
        qi = pad(_rope_first((cq @ lp["wiq"]).reshape(s, hi, di), pos,
                             theta, rope))
        w = pad((h @ lp["wiw"]) * (hi ** -0.5 * di ** -0.5))

    def block(i):
        cut = lambda a: jax.lax.dynamic_slice_in_dim(a, i * q_block, q_block,
                                                     0)
        if window is None:
            see = selection(index_matrix(cut(qi), ki, cut(w), i * q_block),
                            topk)
        else:
            qpos = (i * q_block + jnp.arange(q_block))[:, None]
            see = (pos[None, :] <= qpos) & (pos[None, :] > qpos - window)
        sc = jnp.einsum("qhd,shd->hqs", cut(qf), k) * scale
        sc = jnp.where(see[None], sc, -jnp.inf)
        # (a pad row past the sequence's end still sees position 0 or its
        # own band: every row has a finite score)
        return jnp.einsum("hqs,shd->qhd", jax.nn.softmax(sc, axis=-1), v)

    out = jax.lax.map(block, jnp.arange(nblk)).reshape(
        nblk * q_block, hq, vd)[:s]
    return (out * gate[:, :, None]).reshape(s, hq * vd) @ lp["wo"]


@functools.partial(jax.jit, static_argnames=(
    "hq", "rank", "nope", "rope", "vd", "theta", "s_q", "s_kv", "eps",
    "latent_eps", "window", "hi", "di", "topk", "index_eps", "q_block"))
def _attn_layer(x, lp, *, eps, **kw):
    with jax.default_matmul_precision("highest"):
        lp = jax.tree.map(lambda a: a.astype(F32), lp)
        return x + _latent_attention(_rms(x, lp["ln1"], eps), lp, **kw)


def geometry(cfg: Dict, kind: str) -> Dict:
    """The static arguments of one kind of layer from the published keys."""
    pre = "swa_" if kind == "sliding_attention" else ""
    hidden = int(cfg["hidden_size"])
    rank, qr = int(cfg[pre + "kv_lora_rank"]), int(cfg[pre + "q_lora_rank"])
    rescale = bool(cfg.get("apply_mla_qkv_lora_rescale", False))
    theta = cfg["swa_rope_theta"] if pre else cfg["rope_theta"]
    return dict(
        hq=int(cfg[pre + "num_attention_heads"]), rank=rank,
        nope=int(cfg[pre + "qk_nope_head_dim"]),
        rope=int(cfg[pre + "qk_rope_head_dim"]),
        vd=int(cfg[pre + "v_head_dim"]), theta=float(theta),
        s_q=float((hidden / qr) ** 0.5) if rescale else 1.0,
        s_kv=float((hidden / rank) ** 0.5) if rescale else 1.0,
        latent_eps=float(cfg.get("latent_norm_eps", 1e-6)),
        window=int(cfg["sliding_window_size"]) if pre else None,
        hi=int(cfg["index_n_heads"]), di=int(cfg["index_head_dim"]),
        topk=int(cfg["index_topk"]),
        index_eps=float(cfg.get("index_norm_eps", 1e-6)))


def _check(cfg: Dict) -> None:
    kinds = list(cfg["layer_types"])
    if len(kinds) != int(cfg["num_hidden_layers"]) or set(kinds) - set(KINDS):
        raise ValueError(f"layer_types: {cfg['num_hidden_layers']} entries "
                         f"of {' | '.join(KINDS)} wanted, got {kinds}")
    for key in ("attention_gate_type", "swa_attention_gate_type"):
        if cfg.get(key, "headwise") != "headwise":
            raise ValueError(f"reference/dots3_note.py: {key}={cfg[key]!r}; "
                             f"the headwise gate is what is implemented")
    if cfg.get("rope_scaling") is not None:
        raise ValueError("reference/dots3_note.py: plain rope only")
    # the FFN blocks' and the router's refusals are Moonlight's own
    base._check({**cfg, "q_lora_rank": None})


def hidden(params: Dict, ids: np.ndarray, cfg: Dict, q_block: int = 256,
           expert_block: int = 4) -> jnp.ndarray:
    """The residual stream [S, H] after the last layer of ONE sequence."""
    _check(cfg)
    s = int(ids.shape[0])
    eps = float(cfg["rms_norm_eps"])
    x = base._embed(params["embed"], np.asarray(ids, np.int32))
    for kind, lp in zip(cfg["layer_types"], params["layers"]):
        keys = _ATTN_KEYS + (_INDEX_KEYS if kind == "full_attention" else ())
        x = _attn_layer(x, {k: lp[k] for k in keys}, eps=eps,
                        q_block=min(q_block, s), **geometry(cfg, kind))
        x = _ffn(x, lp, cfg, eps, expert_block)
    return x


def logits_at(params: Dict, ids: np.ndarray, cfg: Dict,
              rows: Sequence[int], q_block: int = 256) -> np.ndarray:
    """Next-token logits [len(rows), vocab] of ONE sequence ``ids`` [S]
    after a full forward pass, at the given positions."""
    x = hidden(params, ids, cfg, q_block)[np.asarray(rows)]
    return np.asarray(base._logits(x, params["norm"], params["lm_head"],
                                   eps=float(cfg["rms_norm_eps"])),
                      np.float32)
