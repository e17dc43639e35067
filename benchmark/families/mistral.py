"""Mistral family adapter: from the published ``config.json`` keys to the
program's model objects, to the plain reference's parameter dict, and to the
shape facts the FLOP/byte functions need.  The only file that knows both
namings."""

from __future__ import annotations

from typing import Any, Dict

REFERENCE = "mistral"


def program_config(hf: Dict[str, Any]):
    import jax.numpy as jnp

    from deepspeed_tpu.models.mistral import MistralConfig

    return MistralConfig(
        vocab_size=hf["vocab_size"], hidden_size=hf["hidden_size"],
        intermediate_size=hf["intermediate_size"],
        num_hidden_layers=hf["num_hidden_layers"],
        num_attention_heads=hf["num_attention_heads"],
        num_key_value_heads=hf["num_key_value_heads"],
        max_position_embeddings=hf["max_position_embeddings"],
        rms_norm_eps=hf["rms_norm_eps"], rope_theta=hf["rope_theta"],
        sliding_window=hf["sliding_window"],
        tie_word_embeddings=hf["tie_word_embeddings"], dtype=jnp.bfloat16)


def train_model(hf: Dict[str, Any]):
    from deepspeed_tpu.models.mistral import MistralForCausalLM

    return MistralForCausalLM(program_config(hf))


def serve_model(hf: Dict[str, Any], block_size: int, mesh=None):
    from deepspeed_tpu.inference.v2.model_implementations import RaggedLlama

    return RaggedLlama(program_config(hf), block_size, mesh=mesh)


def serve_param_shapes(hf: Dict[str, Any]):
    """The parameter tree the serving engine expects, as shapes."""
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.models.llama import LlamaForCausalLM

    return jax.eval_shape(
        LlamaForCausalLM(program_config(hf)).init, jax.random.key(0),
        jax.ShapeDtypeStruct((1, 8), jnp.int32))["params"]


def init_std(path_names, shape) -> Any:
    """Seeded-weight scale per leaf: norm scales 1 (None), embedding
    N(0, 0.02^2), kernels N(0, 1/fan_in)."""
    leaf = path_names[-1]
    if leaf == "scale":
        return None
    if leaf == "embedding":
        return 0.02
    return shape[0] ** -0.5


def reference_params(params) -> Dict[str, Any]:
    """Program tree -> the plain reference's dict (no copy, no cast)."""
    m = params["model"]
    n = sum(1 for k in m if k.startswith("layers_"))
    layers = []
    for i in range(n):
        lp = m[f"layers_{i}"]
        layers.append({
            "ln1": lp["input_layernorm"]["scale"],
            "ln2": lp["post_attention_layernorm"]["scale"],
            "wq": lp["self_attn"]["q_proj"]["kernel"],
            "wk": lp["self_attn"]["k_proj"]["kernel"],
            "wv": lp["self_attn"]["v_proj"]["kernel"],
            "wo": lp["self_attn"]["o_proj"]["kernel"],
            "w_gate": lp["mlp"]["gate_proj"]["kernel"],
            "w_up": lp["mlp"]["up_proj"]["kernel"],
            "w_down": lp["mlp"]["down_proj"]["kernel"]})
    return {"embed": m["embed_tokens"]["embedding"], "layers": layers,
            "norm": m["norm"]["scale"], "lm_head": params["lm_head"]["kernel"]}


def shapes(hf: Dict[str, Any]) -> Dict[str, int]:
    """Shape facts for ``lib/costs.py``.  ``matmul_params`` leaves out the
    embedding lookup (a gather, no FLOPs) and counts the lm_head."""
    h, i, v = hf["hidden_size"], hf["intermediate_size"], hf["vocab_size"]
    hq, hkv = hf["num_attention_heads"], hf["num_key_value_heads"]
    d = h // hq
    per_layer = h * hq * d + 2 * h * hkv * d + hq * d * h + 3 * h * i
    layers = hf["num_hidden_layers"]
    return {"layers": layers, "hidden": h, "q_heads": hq, "kv_heads": hkv,
            "head_dim": d, "vocab": v,
            "matmul_params": layers * per_layer + h * v,
            "total_params": layers * (per_layer + 2 * h) + 2 * h * v + h,
            "kv_bytes_per_token": 2 * layers * hkv * d * 2}
