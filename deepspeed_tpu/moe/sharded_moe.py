"""Sharded MoE: gating + expert dispatch (reference: deepspeed/moe/
sharded_moe.py — ``top1gating:184``, ``top2gating:282``, ``TopKGate:348``,
``MOELayer:425`` with einsum dispatch and ``_AllToAll:95``).

GShard-style einsum dispatch, TPU-first: the token->expert permutation is a
pair of einsums over a [tokens, experts, capacity] one-hot dispatch tensor,
and expert parallelism is a sharding constraint on the expert dimension —
XLA lowers the re-partition to an ICI all-to-all (the reference's explicit
``_AllToAll`` autograd op). Static capacity keeps every shape
compile-constant, which is what makes this formulation fast on TPU.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Callable, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P


def _one_hot(idx, num: int, dtype=jnp.float32):
    return jax.nn.one_hot(idx, num, dtype=dtype)


def _capacity(num_tokens: int, num_experts: int, capacity_factor: float,
              min_capacity: int) -> int:
    # ceil, matching the reference (sharded_moe.py _capacity): truncation
    # would drop extra tokens whenever tokens/experts*factor is fractional.
    cap = math.ceil(num_tokens / num_experts * capacity_factor)
    return max(cap, min_capacity)


def top1gating(logits, capacity_factor: float = 1.0, min_capacity: int = 4,
               noisy_gate_policy: Optional[str] = None,
               rng: Optional[jax.Array] = None,
               drop_tokens: bool = True):
    """reference top1gating (sharded_moe.py:184). Returns
    (l_aux, combine [S,E,C], dispatch [S,E,C] bool)."""
    s, e = logits.shape
    c = _capacity(s, e, capacity_factor, min_capacity)
    gating_logits = logits
    if noisy_gate_policy == "RSample" and rng is not None:
        gating_logits = logits + jax.random.gumbel(rng, logits.shape,
                                                   logits.dtype)
    gates = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    expert_idx = jnp.argmax(gating_logits, axis=-1)  # [S]
    mask1 = _one_hot(expert_idx, e)  # [S,E]

    # load-balancing aux loss (GShard eq.): E * sum_e(frac_tokens * frac_prob)
    me = jnp.mean(gates, axis=0)
    ce = jnp.mean(mask1, axis=0)
    l_aux = jnp.sum(me * ce) * e

    # position of each token within its expert's queue
    position_in_expert = jnp.cumsum(mask1, axis=0) * mask1 - mask1  # 0-based
    if drop_tokens:
        mask1 = mask1 * (position_in_expert < c)
    pos = jnp.sum(position_in_expert * mask1, axis=-1)  # [S]

    gate_val = jnp.sum(gates * mask1, axis=-1)  # [S], 0 for dropped
    dispatch = (mask1[:, :, None] *
                _one_hot(pos.astype(jnp.int32), c)[:, None, :])  # [S,E,C]
    combine = gate_val[:, None, None] * dispatch
    return l_aux, combine, dispatch.astype(bool)


def top2gating(logits, capacity_factor: float = 1.0, min_capacity: int = 4,
               rng: Optional[jax.Array] = None, drop_tokens: bool = True,
               top2_2nd_expert_sampling: bool = True):
    """reference top2gating (sharded_moe.py:282)."""
    s, e = logits.shape
    c = _capacity(s, e, 2 * capacity_factor, min_capacity)
    gates = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)

    idx1 = jnp.argmax(gates, axis=-1)
    mask1 = _one_hot(idx1, e)
    logits2 = logits.astype(jnp.float32)
    if top2_2nd_expert_sampling and rng is not None:
        logits2 = logits2 + jax.random.gumbel(rng, logits2.shape)
    logits2 = jnp.where(mask1.astype(bool), -jnp.inf, logits2)
    idx2 = jnp.argmax(logits2, axis=-1)
    mask2 = _one_hot(idx2, e)

    me = jnp.mean(gates, axis=0)
    ce = jnp.mean(mask1, axis=0)
    l_aux = jnp.sum(me * ce) * e

    pos1 = jnp.cumsum(mask1, axis=0) * mask1 - mask1
    pos2 = (jnp.cumsum(mask2, axis=0) - 1 +
            jnp.sum(mask1, axis=0, keepdims=True)) * mask2
    if drop_tokens:
        mask1 = mask1 * (pos1 < c)
        mask2 = mask2 * (pos2 < c)

    g1 = jnp.sum(gates * mask1, axis=-1)
    g2 = jnp.sum(gates * mask2, axis=-1)
    denom = jnp.maximum(g1 + g2, 1e-9)
    g1, g2 = g1 / denom, g2 / denom

    p1 = jnp.sum(pos1 * mask1, axis=-1).astype(jnp.int32)
    p2 = jnp.sum(pos2 * mask2, axis=-1).astype(jnp.int32)
    d1 = mask1[:, :, None] * _one_hot(p1, c)[:, None, :]
    d2 = mask2[:, :, None] * _one_hot(p2, c)[:, None, :]
    combine = g1[:, None, None] * d1 + g2[:, None, None] * d2
    dispatch = (d1 + d2) > 0
    return l_aux, combine, dispatch


class TopKGate(nn.Module):
    """reference TopKGate (sharded_moe.py:348): linear router in fp32."""

    num_experts: int
    k: int = 1
    capacity_factor: float = 1.0
    eval_capacity_factor: float = 1.0
    min_capacity: int = 4
    noisy_gate_policy: Optional[str] = None
    drop_tokens: bool = True
    norm_topk_prob: bool = True       # dropless path only

    @nn.compact
    def __call__(self, x, train: bool = True, rng=None,
                 dropless: bool = False):
        logits = nn.Dense(self.num_experts, use_bias=False,
                          dtype=jnp.float32, param_dtype=jnp.float32,
                          name="wg")(x.astype(jnp.float32))
        if dropless:
            # Megablocks-style routing: exact top-k (weights renormalised
            # unless norm_topk_prob is off), NO capacity buckets (grouped GEMM handles the
            # ragged per-expert token counts).  Returns
            # (l_aux, topi [S,k], topw [S,k]).
            from deepspeed_tpu.ops.grouped_gemm import exact_topk_routing

            topi, topw = exact_topk_routing(logits, self.k,
                                            self.norm_topk_prob)
            probs = jax.nn.softmax(logits, axis=-1)
            me = jnp.mean(probs, axis=0)
            ce = jnp.mean(
                jnp.sum(jax.nn.one_hot(topi, self.num_experts), axis=1),
                axis=0) / self.k
            l_aux = jnp.sum(me * ce) * self.num_experts
            return l_aux, topi, topw
        cf = self.capacity_factor if train else self.eval_capacity_factor
        if self.k == 1:
            return top1gating(logits, cf, self.min_capacity,
                              self.noisy_gate_policy if train else None,
                              rng, self.drop_tokens)
        if self.k == 2:
            return top2gating(logits, cf, self.min_capacity, rng,
                              self.drop_tokens)
        raise ValueError(f"k={self.k} not supported (reference supports 1/2)")


class ExpertsFFN(nn.Module):
    """Per-expert SwiGLU FFN, weights stacked on a leading expert dim so the
    expert matmuls are one grouped einsum on the MXU (reference
    moe/experts.py wraps E copies; stacking is the TPU-native layout)."""

    num_experts: int
    hidden: int
    intermediate: int
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x, grouped=None):
        """x: [E, C, M] (capacity-dispatched) -> [E, C, M]; or, with
        ``grouped=(topi, topw)``, x: [S, M] flat tokens -> [S, M] through
        the grouped GEMM kernel (dropless — same params, no capacity)."""
        init = nn.initializers.lecun_normal()
        w_gate = self.param("w_gate", init,
                            (self.num_experts, self.hidden, self.intermediate),
                            jnp.float32)
        w_up = self.param("w_up", init,
                          (self.num_experts, self.hidden, self.intermediate),
                          jnp.float32)
        w_down = self.param("w_down", init,
                            (self.num_experts, self.intermediate, self.hidden),
                            jnp.float32)
        if grouped is not None:
            from deepspeed_tpu.ops.grouped_gemm import grouped_moe_ffn

            topi, topw = grouped
            return grouped_moe_ffn(
                x.astype(self.dtype), topi, topw.astype(self.dtype),
                w_gate.astype(self.dtype), w_up.astype(self.dtype),
                w_down.astype(self.dtype))
        h = nn.silu(jnp.einsum("ecm,emh->ech", x, w_gate.astype(self.dtype))) * \
            jnp.einsum("ecm,emh->ech", x, w_up.astype(self.dtype))
        return jnp.einsum("ech,ehm->ecm", h, w_down.astype(self.dtype))


class MOELayer(nn.Module):
    """reference MOELayer (sharded_moe.py:425): gate → einsum dispatch →
    (all-to-all) → experts → (all-to-all) → einsum combine."""

    num_experts: int
    hidden: int
    intermediate: int
    k: int = 1
    capacity_factor: float = 1.0
    eval_capacity_factor: float = 1.0
    min_capacity: int = 4
    noisy_gate_policy: Optional[str] = None
    drop_tokens: bool = True
    dtype: Any = jnp.bfloat16
    expert_axis: str = "expert"
    mesh: Any = None
    #: Megablocks-style dropless MoE: exact top-k routing + grouped GEMM
    #: (ops/grouped_gemm.py) instead of capacity dispatch.  No token is
    #: ever dropped and no capacity padding is computed; requires
    #: ep_size == 1 (expert weights replicated or TP-sharded) — the
    #: capacity path remains the expert-parallel all-to-all form.
    dropless: bool = False
    norm_topk_prob: bool = True       # dropless path only

    @nn.compact
    def __call__(self, x, train: bool = True, rng=None):
        """x: [B, S, M] -> (out [B, S, M], l_aux)."""
        b, s, m = x.shape
        tokens = x.reshape(b * s, m)
        if self.dropless:
            mesh = self.mesh
            if mesh is None:
                from deepspeed_tpu.parallel import groups

                if groups.is_initialized():
                    mesh = groups.get_mesh()
            if mesh is not None and mesh.shape.get(self.expert_axis, 1) > 1:
                raise ValueError(
                    "dropless MoE does not compose with expert "
                    "parallelism yet — use the capacity path for ep>1")
            if self.noisy_gate_policy is not None:
                raise ValueError(
                    "dropless MoE uses exact top-k routing; "
                    "noisy_gate_policy is not supported with dropless=True")
            l_aux, topi, topw = TopKGate(
                self.num_experts, self.k,
                norm_topk_prob=self.norm_topk_prob, name="gate")(
                    tokens, train=train, dropless=True)
            out = ExpertsFFN(self.num_experts, self.hidden,
                             self.intermediate, self.dtype,
                             name="experts")(
                tokens.astype(self.dtype), grouped=(topi, topw))
            return out.reshape(b, s, m), l_aux.astype(jnp.float32)
        l_aux, combine, dispatch = TopKGate(
            self.num_experts, self.k, self.capacity_factor,
            self.eval_capacity_factor, self.min_capacity,
            self.noisy_gate_policy, self.drop_tokens, name="gate")(
                tokens, train=train, rng=rng)

        # dispatch: [S,E,C] x [S,M] -> [E,C,M]
        expert_in = jnp.einsum("sec,sm->ecm",
                               dispatch.astype(self.dtype),
                               tokens)
        expert_in = self._expert_sharded(expert_in)
        expert_out = ExpertsFFN(self.num_experts, self.hidden,
                                self.intermediate, self.dtype,
                                name="experts")(expert_in)
        expert_out = self._expert_sharded(expert_out)
        out = jnp.einsum("sec,ecm->sm", combine.astype(self.dtype), expert_out)
        return out.reshape(b, s, m), l_aux.astype(jnp.float32)

    def _expert_sharded(self, t):
        """Constrain [E,C,M] to be expert-sharded; with tokens previously
        batch-sharded this re-partition IS the reference's all-to-all."""
        mesh = self.mesh
        if mesh is None:
            from deepspeed_tpu.parallel import groups

            if not groups.is_initialized():
                return t
            mesh = groups.get_mesh()
        if mesh.shape.get(self.expert_axis, 1) == 1:
            return t
        return lax.with_sharding_constraint(
            t, NamedSharding(mesh, P(self.expert_axis, None, None)))
