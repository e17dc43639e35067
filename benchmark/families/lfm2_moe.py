"""LFM2-MoE family adapter: from the published ``config.json`` keys
(``model_type: lfm2_moe``, LiquidAI/LFM2-24B-A2B) to the program's model
object (``RaggedLfm2``), to the plain reference's parameter dict, and to the
shape facts the FLOP/byte functions need.  The only file that knows both
namings.

**A conv-only state leaf.**  A family whose stateful layers keep a
convolution's tail and nothing else fills ``shapes`` as every state-bearing
family does: ``kv_bytes_per_token`` over its ATTENTION layers alone (2 x 2
x 8 x 64 x 2 B = 4,096 here), ``state_bytes_per_seq`` what one sequence
holds whatever its length (8 convolution layers x 2 rows x 2,048 channels x
2 B = 65,536) and ``state_slots`` (when the configuration has a ``serve``
block); ``conv_layers`` / ``attn_layers`` say how the depth divides.
``experts`` equals ``router_width``: every expert is held.

**Seeded weights.**  Embedding N(0, 1), kernels N(0, 1/fan_in) (the
convolution's ``[taps, channels]`` kernel by its taps: N(0, 1/3)), norm
weights 1, the residual-writing kernels (conv ``out_proj``, ``o_proj``,
every ``down``) at 1 / sqrt(2 L) of that (the Qwen3-Next family's
scaled-residual scales, for its reason).

**The embedding at ``EMBED_STD`` x N(0, 1), and no further scale on the
routed experts** (``EXPERT_DOWN`` 1).  The head is the embedding transposed,
so a token's logit for ITS OWN id is ``|e|^2 / rms(x)``: at N(0, 1) that one
logit is seven times the largest of the other 65,535 and is what the check
divides every difference by.  With the Moonlight family's 1/4 on the routed
experts' down projections as well, the routed FFN (70% of the model's
multiplications) was all but invisible: a program that DROPPED the selection
bias read 0.0058 against the limit of 0.03, the clean program 0.0019-0.0025
(v5e, PR 35, call 2).  Readings at other scales (call 3, two seeds each;
clean / bias dropped / tail zeroed): experts 1, embedding 1: 0.0067-0.0069 /
0.020-0.022 / 0.041-0.048; **experts 1, embedding 0.5: 0.015-0.017 /
0.049-0.054 / 0.111-0.114**; experts 1, embedding 0.25: 0.026-0.045 (over
the limit with every layer right); experts 2, embedding 0.5: 0.038-0.051
(over).  The dropped bias reads three times the clean program at every
setting, so the scale only places the pair about the limit.  At experts 1,
embedding 0.6 (call 4): bias dropped 0.0468 / 0.0469, tail zeroed 0.092 /
0.107, SiLU left 0.189 / 0.192, ``B`` / ``C`` exchanged 0.139 / 0.159, and
the clean program over 20 seeds mean 0.0119, standard deviation 0.0043,
largest 0.0203: mean + 4 standard deviations 0.0290, under the limit by a
hair.  The dropped bias hardly varies with the seed and the clean program
does (a flipped routing or none), so the room belongs on the clean side:
**0.7**, where the dropped bias reads 0.0355 / 0.0375, the zeroed tail
0.071 / 0.079, and the clean program over 20 new seeds mean 0.0115,
standard deviation 0.0045, largest 0.0211 (call 6): the clean program's
spread over seeds does not shrink with the scale as the faults do, so over
51 clean readings at 0.6-0.7 the largest is 0.0211 and the limit of 0.03
sits between it and the dropped bias; no scale widens that window.  What
a smaller embedding changes beside the check's sensitivity: the first
layers' outputs are a larger part of the stream they are added to.

**The selection bias** ``expert_bias = BIAS_STD x z``, ``z`` the seeded N(0,
1) leaf; the published buffer is trained from zero.  The spread (0.1
against the sigmoid scores' 0.21) makes a program that DROPS the bias
choose other experts for most tokens (the readings above).  No offset: the
top-k is shift-invariant and the weights never see the bias.
"""

from __future__ import annotations

from typing import Any, Dict

REFERENCE = "lfm2_moe"

#: expert_bias = BIAS_STD * z (the module doc)
BIAS_STD = 0.1
#: the routed experts' down projections, beside the residual scale (1: none)
EXPERT_DOWN = 1.0
#: the embedding (and with it the tied head)
EMBED_STD = 0.7
#: what a residual-writing kernel is scaled by: the 1 / sqrt(2 L) of
#: scaled-residual initialisers at this configuration's L = 10
RESIDUAL_SCALE = 20 ** -0.5


def _head_dim(hf: Dict[str, Any]) -> int:
    return int(hf.get("head_dim")
               or hf["hidden_size"] // hf["num_attention_heads"])


def program_config(hf: Dict[str, Any]):
    import jax.numpy as jnp

    from deepspeed_tpu.inference.v2.model_implementations.ragged_lfm2 \
        import Lfm2Config

    return Lfm2Config(
        vocab_size=hf["vocab_size"], hidden_size=hf["hidden_size"],
        intermediate_size=hf["intermediate_size"],
        moe_intermediate_size=hf["moe_intermediate_size"],
        num_hidden_layers=hf["num_hidden_layers"],
        layer_types=hf["layer_types"],
        num_attention_heads=hf["num_attention_heads"],
        num_key_value_heads=hf["num_key_value_heads"],
        head_dim=_head_dim(hf), conv_L_cache=hf["conv_L_cache"],
        conv_bias=bool(hf["conv_bias"]),
        num_dense_layers=hf["num_dense_layers"],
        num_experts=hf["num_experts"],
        num_experts_per_tok=hf["num_experts_per_tok"],
        norm_topk_prob=bool(hf["norm_topk_prob"]),
        use_expert_bias=bool(hf["use_expert_bias"]),
        routed_scaling_factor=float(hf["routed_scaling_factor"]),
        router_norm_eps=float(hf.get("router_norm_eps", 1e-6)),
        norm_eps=hf["norm_eps"],
        rope_theta=float(hf["rope_parameters"]["rope_theta"]),
        max_position_embeddings=hf["max_position_embeddings"],
        tie_embedding=bool(hf.get("tie_embedding", True)),
        dtype=jnp.bfloat16)


def serve_model(hf: Dict[str, Any], block_size: int, mesh=None):
    from deepspeed_tpu.inference.v2.model_implementations.ragged_lfm2 \
        import RaggedLfm2

    return RaggedLfm2(program_config(hf), block_size)


def serve_param_shapes(hf: Dict[str, Any]):
    from deepspeed_tpu.inference.v2.model_implementations.ragged_lfm2 \
        import param_shapes

    return param_shapes(program_config(hf))


def init_std(path_names, shape) -> Any:
    """Seeded-weight scale per leaf (the module doc)."""
    leaf, parent = path_names[-1], path_names[-2] if len(path_names) > 1 \
        else ""
    if leaf == "scale":
        return None
    if leaf == "embedding":
        return EMBED_STD
    if leaf == "e_score_correction_bias":
        return BIAS_STD
    if leaf == "w_down":
        return EXPERT_DOWN * RESIDUAL_SCALE * shape[1] ** -0.5
    if leaf in ("w_gate", "w_up"):
        return shape[1] ** -0.5
    if parent in ("out_proj", "o_proj", "down_proj"):
        return RESIDUAL_SCALE * shape[0] ** -0.5
    # (the convolution's [taps, channels] kernel: fan-in = taps)
    return shape[0] ** -0.5


def reference_params(params) -> Dict[str, Any]:
    """Program tree -> the plain reference's dict (no copy, no cast)."""
    n = sum(1 for k in params if k.startswith("layers_"))
    layers = []
    for i in range(n):
        lp = params[f"layers_{i}"]
        mlp = lp["mlp"]
        layer = {"ln1": lp["operator_norm"]["scale"],
                 "ln2": lp["ffn_norm"]["scale"]}
        if "conv" in lp:
            cv = lp["conv"]
            layer.update({"w_in": cv["in_proj"]["kernel"],
                          "taps": cv["conv1d"]["kernel"],
                          "w_out": cv["out_proj"]["kernel"]})
        else:
            att = lp["self_attn"]
            layer.update({
                "wq": att["q_proj"]["kernel"], "wk": att["k_proj"]["kernel"],
                "wv": att["v_proj"]["kernel"], "wo": att["o_proj"]["kernel"],
                "q_norm": att["q_norm"]["scale"],
                "k_norm": att["k_norm"]["scale"]})
        if "gate" in mlp:
            layer.update({
                "router": mlp["gate"]["wg"]["kernel"],
                "bias": mlp["gate"]["e_score_correction_bias"],
                "w_gate": mlp["experts"]["w_gate"],
                "w_up": mlp["experts"]["w_up"],
                "w_down": mlp["experts"]["w_down"]})
        else:
            layer.update({"gate": mlp["gate_proj"]["kernel"],
                          "up": mlp["up_proj"]["kernel"],
                          "down": mlp["down_proj"]["kernel"]})
        layers.append(layer)
    return {"embed": params["embed_tokens"]["embedding"], "layers": layers,
            "norm": params["norm"]["scale"]}


def shapes(hf: Dict[str, Any]) -> Dict[str, int]:
    """Shape facts for ``lib/costs.py``, ``lib/costs_moe.py`` and
    ``lib/costs_paged.py`` (the module doc says how a family with a
    conv-only state leaf fills them).  ``matmul_params`` counts what one
    token multiplies by: the mixers, the dense layers' FFNs, per MoE layer
    the router and ``experts_per_token`` experts, and the head (the tied
    embedding, once)."""
    h, v = hf["hidden_size"], hf["vocab_size"]
    hq, hkv, d = hf["num_attention_heads"], hf["num_key_value_heads"], \
        _head_dim(hf)
    e, k, f, fd = hf["num_experts"], hf["num_experts_per_tok"], \
        hf["moe_intermediate_size"], hf["intermediate_size"]
    layers, taps = hf["num_hidden_layers"], hf["conv_L_cache"]
    attn_layers = sum(t == "full_attention" for t in hf["layer_types"])
    conv_layers = layers - attn_layers
    dense = min(int(hf["num_dense_layers"]), layers)
    moe_layers = layers - dense
    attn = 2 * h * hq * d + 2 * h * hkv * d
    conv = 4 * h * h
    out = {"layers": layers, "hidden": h, "q_heads": hq, "kv_heads": hkv,
           "head_dim": d, "vocab": v,
           "attn_layers": attn_layers, "conv_layers": conv_layers,
           "conv_taps": taps, "dense_layers": dense,
           "moe_layers": moe_layers,
           "experts": e, "router_width": e, "experts_per_token": k,
           "expert_width": f,
           "matmul_params": attn_layers * attn + conv_layers * conv
           + dense * 3 * h * fd + moe_layers * (h * e + k * 3 * h * f)
           + h * v,
           "total_params": attn_layers * (attn + 2 * d)
           + conv_layers * (conv + taps * h) + dense * 3 * h * fd
           + moe_layers * (h * e + e + e * 3 * h * f) + layers * 2 * h
           + h * v + h,
           "kv_bytes_per_token": 2 * attn_layers * hkv * d * 2,
           "state_bytes_per_seq": conv_layers * (taps - 1) * h * 2}
    if "serve" in hf:
        out["state_slots"] = int(hf["serve"]["max_ragged_sequence_count"])
    return out
