"""GPT-2 family adapter (see ``families/mistral.py`` for the roles)."""

from __future__ import annotations

from typing import Any, Dict

REFERENCE = "gpt2"


def program_config(hf: Dict[str, Any]):
    import jax.numpy as jnp

    from deepspeed_tpu.models.gpt2 import GPT2Config

    # no attention_layout key: what a user of the program gets by default
    return GPT2Config(
        vocab_size=hf["vocab_size"], hidden_size=hf["n_embd"],
        num_hidden_layers=hf["n_layer"], num_attention_heads=hf["n_head"],
        max_position_embeddings=hf["n_positions"],
        layer_norm_epsilon=hf["layer_norm_epsilon"],
        embd_pdrop=0.0, attn_pdrop=0.0, resid_pdrop=0.0,
        intermediate_size=hf.get("n_inner"), dtype=jnp.bfloat16)


def train_model(hf: Dict[str, Any]):
    from deepspeed_tpu.models.gpt2 import GPT2LMHeadModel

    return GPT2LMHeadModel(program_config(hf))


def reference_params(params) -> Dict[str, Any]:
    n = sum(1 for k in params if k.startswith("h_"))
    layers = []
    for i in range(n):
        lp = params[f"h_{i}"]
        layers.append({
            "ln1_g": lp["ln_1"]["scale"], "ln1_b": lp["ln_1"]["bias"],
            "ln2_g": lp["ln_2"]["scale"], "ln2_b": lp["ln_2"]["bias"],
            "c_attn_w": lp["c_attn"]["kernel"],
            "c_attn_b": lp["c_attn"]["bias"],
            "attn_out_w": lp["attn_out"]["kernel"],
            "attn_out_b": lp["attn_out"]["bias"],
            "c_fc_w": lp["c_fc"]["kernel"], "c_fc_b": lp["c_fc"]["bias"],
            "c_proj_w": lp["c_proj"]["kernel"],
            "c_proj_b": lp["c_proj"]["bias"]})
    return {"wte": params["wte"]["embedding"],
            "wpe": params["wpe"]["embedding"], "layers": layers,
            "lnf_g": params["ln_f"]["scale"], "lnf_b": params["ln_f"]["bias"]}


def shapes(hf: Dict[str, Any]) -> Dict[str, int]:
    """``matmul_params`` counts the tied unembedding matmul once (it is a
    real [hidden, vocab] GEMM) and leaves out both embedding lookups."""
    h, v = hf["n_embd"], hf["vocab_size"]
    inner = hf.get("n_inner") or 4 * h
    heads = hf["n_head"]
    per_layer = 3 * h * h + h * h + 2 * h * inner
    layers = hf["n_layer"]
    biases = 3 * h + h + inner + h + 4 * h
    return {"layers": layers, "hidden": h, "q_heads": heads,
            "kv_heads": heads, "head_dim": h // heads, "vocab": v,
            "matmul_params": layers * per_layer + h * v,
            "total_params": layers * (per_layer + biases)
            + v * h + hf["n_positions"] * h + 2 * h,
            "kv_bytes_per_token": 2 * layers * h * 2}
