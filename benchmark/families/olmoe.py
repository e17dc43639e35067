"""OLMoE family adapter: from the published ``config.json`` keys
(``model_type: olmoe``) to the program's model objects (the Mixtral classes
with ``qk_norm`` on and ``norm_topk_prob`` as published), to the plain
reference's parameter dict, and to the shape facts the FLOP/byte functions
need.  The only file that knows both namings."""

from __future__ import annotations

from typing import Any, Dict

REFERENCE = "olmoe"


def program_config(hf: Dict[str, Any]):
    import jax.numpy as jnp

    from deepspeed_tpu.models.mixtral import MixtralConfig

    # (clip_qkv, attention_bias, rope_scaling: unset in the published
    # config; the reference refuses a configuration that sets one)
    return MixtralConfig(
        vocab_size=hf["vocab_size"], hidden_size=hf["hidden_size"],
        intermediate_size=hf["intermediate_size"],
        num_hidden_layers=hf["num_hidden_layers"],
        num_attention_heads=hf["num_attention_heads"],
        num_key_value_heads=hf["num_key_value_heads"],
        max_position_embeddings=hf["max_position_embeddings"],
        rms_norm_eps=hf["rms_norm_eps"], rope_theta=float(hf["rope_theta"]),
        num_local_experts=hf["num_experts"],
        num_experts_per_tok=hf["num_experts_per_tok"],
        norm_topk_prob=bool(hf["norm_topk_prob"]), qk_norm=True,
        dtype=jnp.bfloat16)


def train_model(hf: Dict[str, Any]):
    from deepspeed_tpu.models.mixtral import MixtralForCausalLM

    return MixtralForCausalLM(program_config(hf))


def serve_model(hf: Dict[str, Any], block_size: int, mesh=None):
    from deepspeed_tpu.inference.v2.model_implementations.ragged_mixtral \
        import RaggedMixtral

    if mesh is not None:
        raise ValueError("RaggedMixtral serves one chip (TP = 1)")
    return RaggedMixtral(program_config(hf), block_size)


def serve_param_shapes(hf: Dict[str, Any]):
    """The parameter tree the serving engine expects, as shapes: the
    training model's, by contract."""
    import jax
    import jax.numpy as jnp

    return jax.eval_shape(
        train_model(hf).init, jax.random.key(0),
        jax.ShapeDtypeStruct((1, 8), jnp.int32))["params"]


def init_std(path_names, shape) -> Any:
    """Seeded-weight scale per leaf: norm scales 1 (None), embedding
    N(0, 0.02^2), kernels N(0, 1/fan_in); the stacked expert matrices
    [E, in, out] have their fan-in in the second place."""
    leaf = path_names[-1]
    if leaf == "scale":
        return None
    if leaf == "embedding":
        return 0.02
    if leaf in ("w_gate", "w_up", "w_down"):
        return shape[1] ** -0.5
    return shape[0] ** -0.5


def reference_params(params) -> Dict[str, Any]:
    """Program tree -> the plain reference's dict (no copy, no cast)."""
    n = sum(1 for k in params if k.startswith("layers_"))
    layers = []
    for i in range(n):
        lp = params[f"layers_{i}"]
        att = lp["self_attn"]
        moe = lp["block_sparse_moe"]["deepspeed_moe"]
        layers.append({
            "ln1": lp["input_layernorm"]["scale"],
            "ln2": lp["post_attention_layernorm"]["scale"],
            "wq": att["q_proj"]["kernel"], "wk": att["k_proj"]["kernel"],
            "wv": att["v_proj"]["kernel"], "wo": att["o_proj"]["kernel"],
            "q_norm": att["q_norm"]["scale"],
            "k_norm": att["k_norm"]["scale"],
            "router": moe["gate"]["wg"]["kernel"],
            "w_gate": moe["experts"]["w_gate"],
            "w_up": moe["experts"]["w_up"],
            "w_down": moe["experts"]["w_down"]})
    return {"embed": params["embed_tokens"]["embedding"], "layers": layers,
            "norm": params["norm"]["scale"],
            "lm_head": params["lm_head"]["kernel"]}


def shapes(hf: Dict[str, Any]) -> Dict[str, int]:
    """Shape facts for ``lib/costs.py`` and ``lib/costs_moe.py``.
    ``matmul_params`` counts what ONE token multiplies by: the attention
    projections, the router, its ``experts_per_token`` experts and the
    lm_head (the embedding lookup is a gather); ``total_params`` counts
    every expert."""
    h, f, v = hf["hidden_size"], hf["intermediate_size"], hf["vocab_size"]
    hq, hkv = hf["num_attention_heads"], hf["num_key_value_heads"]
    e, k = hf["num_experts"], hf["num_experts_per_tok"]
    d = h // hq
    attn = h * hq * d + 2 * h * hkv * d + hq * d * h
    norms = 2 * h + hq * d + hkv * d
    layers = hf["num_hidden_layers"]
    return {"layers": layers, "hidden": h, "q_heads": hq, "kv_heads": hkv,
            "head_dim": d, "vocab": v,
            "experts": e, "experts_per_token": k, "expert_width": f,
            "matmul_params": layers * (attn + h * e + k * 3 * h * f) + h * v,
            "total_params": layers * (attn + h * e + e * 3 * h * f + norms)
            + 2 * h * v + h,
            "kv_bytes_per_token": 2 * layers * hkv * d * 2}
