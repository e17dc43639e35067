"""Plain reference for the GPT-2 block (openai-community/gpt2-large as
published: learned position embeddings, pre-LayerNorm blocks with biases,
fused c_attn projection, multi-head causal attention, gelu_new MLP, tied
embedding / unembedding).

Straightforward float32 ``jax.numpy`` under
``default_matmul_precision("highest")``, layer by layer, no kernels, no
import from ``deepspeed_tpu``; one sequence at a time.

Parameters: ``{"wte": [V, H], "wpe": [P, H], "layers": [{"ln1_g", "ln1_b",
"ln2_g", "ln2_b", "c_attn_w" [H, 3H], "c_attn_b", "attn_out_w", "attn_out_b",
"c_fc_w", "c_fc_b", "c_proj_w", "c_proj_b"}, ...], "lnf_g", "lnf_b"}``.
"""

from __future__ import annotations

import functools
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32


def _ln(x, g, b, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * g + b


def _gelu_new(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        np.sqrt(2.0 / np.pi) * (x + 0.044715 * x ** 3)))


@functools.partial(jax.jit, static_argnames=("heads", "eps"))
def _layer(x, lp, *, heads, eps):
    with jax.default_matmul_precision("highest"):
        lp = jax.tree.map(lambda a: a.astype(F32), lp)
        s, hid = x.shape
        d = hid // heads
        h = _ln(x, lp["ln1_g"], lp["ln1_b"], eps)
        qkv = h @ lp["c_attn_w"] + lp["c_attn_b"]
        q, k, v = (t.reshape(s, heads, d) for t in jnp.split(qkv, 3, -1))
        sc = jnp.einsum("qhd,khd->hqk", q, k) / np.sqrt(d)
        keep = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
        p = jax.nn.softmax(jnp.where(keep[None], sc, -jnp.inf), axis=-1)
        a = jnp.einsum("hqk,khd->qhd", p, v).reshape(s, hid)
        x = x + a @ lp["attn_out_w"] + lp["attn_out_b"]
        h = _ln(x, lp["ln2_g"], lp["ln2_b"], eps)
        h = _gelu_new(h @ lp["c_fc_w"] + lp["c_fc_b"])
        return x + h @ lp["c_proj_w"] + lp["c_proj_b"]


@jax.jit
def _embed(wte, wpe, ids):
    return (wte[ids] + wpe[jnp.arange(ids.shape[0])]).astype(F32)


@functools.partial(jax.jit, static_argnames=("eps",))
def _nll_sum(x, g, b, wte, targets, *, eps):
    with jax.default_matmul_precision("highest"):
        logits = _ln(x, g.astype(F32), b.astype(F32), eps) @ wte.astype(F32).T
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, targets[:, None], axis=-1)[:, 0]
        return jnp.sum(logz - gold)


def loss(params: Dict, ids: np.ndarray, cfg: Dict) -> float:
    """Mean next-token cross-entropy over a batch ``ids`` [B, S]."""
    eps = float(cfg["layer_norm_epsilon"])
    total, count = 0.0, 0
    for seq in np.asarray(ids):
        x = _embed(params["wte"], params["wpe"], np.asarray(seq, np.int32))
        for lp in params["layers"]:
            x = _layer(x, lp, heads=int(cfg["n_head"]), eps=eps)
        total += float(_nll_sum(x[:-1], params["lnf_g"], params["lnf_b"],
                                params["wte"],
                                np.asarray(seq[1:], np.int32), eps=eps))
        count += len(seq) - 1
    return total / count
