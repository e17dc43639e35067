"""MoE layer front-end (reference: deepspeed/moe/layer.py:16 ``MoE``).

Wraps the sharded MOELayer with the reference's constructor surface
(num_experts, ep_size, k, capacity factors, residual MoE). Expert parallelism
degree comes from the mesh's 'expert' axis; ``ep_size`` is validated against
it rather than creating process groups (reference
``_create_expert_and_data_parallel``, utils/groups.py:113).
"""

from __future__ import annotations

from typing import Any, Optional

import flax.linen as nn
import jax.numpy as jnp

from deepspeed_tpu.moe.sharded_moe import MOELayer


class MoE(nn.Module):
    hidden_size: int
    intermediate_size: int
    num_experts: int = 1
    ep_size: int = 1
    k: int = 1
    capacity_factor: float = 1.0
    eval_capacity_factor: float = 1.0
    min_capacity: int = 4
    use_residual: bool = False
    noisy_gate_policy: Optional[str] = None
    drop_tokens: bool = True
    dtype: Any = jnp.bfloat16
    mesh: Any = None
    #: Megablocks-style dropless routing via the grouped GEMM kernel
    #: (ops/grouped_gemm.py); see MOELayer.dropless
    dropless: bool = False
    #: dropless routing only: renormalise the top-k weights (HF Mixtral)
    #: or keep the softmax's own (OLMoE ``norm_topk_prob: false``)
    norm_topk_prob: bool = True

    def _validate(self):
        if self.num_experts % max(1, self.ep_size) != 0:
            raise ValueError(
                f"num_experts {self.num_experts} must be divisible by "
                f"ep_size {self.ep_size}")

    @nn.compact
    def __call__(self, hidden_states, train: bool = True, rng=None):
        self._validate()
        out, l_aux = MOELayer(
            num_experts=self.num_experts, hidden=self.hidden_size,
            intermediate=self.intermediate_size, k=self.k,
            capacity_factor=self.capacity_factor,
            eval_capacity_factor=self.eval_capacity_factor,
            min_capacity=self.min_capacity,
            noisy_gate_policy=self.noisy_gate_policy,
            drop_tokens=self.drop_tokens, dtype=self.dtype, mesh=self.mesh,
            dropless=self.dropless, norm_topk_prob=self.norm_topk_prob,
            name="deepspeed_moe")(hidden_states, train=train, rng=rng)
        if self.use_residual:
            # reference residual MoE (PR-MoE): dense FFN + learned mix
            res = nn.Dense(self.intermediate_size, use_bias=False,
                           dtype=self.dtype, param_dtype=jnp.float32,
                           name="residual_fc1")(hidden_states)
            res = nn.Dense(self.hidden_size, use_bias=False, dtype=self.dtype,
                           param_dtype=jnp.float32,
                           name="residual_fc2")(nn.gelu(res))
            coef = nn.Dense(2, dtype=jnp.float32, param_dtype=jnp.float32,
                            name="coefficient")(
                hidden_states.astype(jnp.float32))
            coef = nn.softmax(coef, axis=-1).astype(self.dtype)
            out = out * coef[..., 0:1] + res * coef[..., 1:2]
        return out, l_aux
