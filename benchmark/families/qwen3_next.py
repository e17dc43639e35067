"""Qwen3-Next family adapter: from the published ``config.json`` keys
(``model_type: qwen3_next``) to the program's model object
(``RaggedQwen3Next``), to the plain reference's parameter dict, and to the
shape facts the FLOP/byte functions need.  The only file that knows both
namings.

**The share.**  ``num_experts`` in the configuration file is how many
experts are HELD here (``reduced``); ``router_experts`` beside it is the
published count, the router's width; ``expert_start`` the first held id.

**The DeltaNet projections' layout.**  The program (and so the reference
dict built here) keeps ``in_proj_qkvz`` as ``q | k | v | z`` and
``in_proj_ba`` as ``b | a`` (all heads of one before the next).  The
published checkpoint interleaves them per key head; that matters to a
loader (``deepspeed_tpu/checkpoint/hf_loader.py`` regroups it) and not to
seeded weights.

**Seeded decay.**  The runner makes every leaf as N(0, std^2), ones or zeros
from the seed.  With the published initialiser (``A`` uniform on (0, 16),
``dt_bias`` 1) nearly every head forgets within a token, and a state or a
convolution tail dropped at a chunk boundary would pass the logits check.
So the served model reads ``dt_bias = DT_SHIFT + DT_SCALE * z`` from its
seeded N(0, 1) leaf ``z`` and ``A_log = 0`` (std 0): at ``a = 0`` a head's
decay is ``exp(g) = exp(-softplus(dt_bias))``.  ``DT_SHIFT`` is set from
what the check has to see: it compares logits 512 tokens after the
boundary between its two prefill chunks, so a good share of the heads must
remember further back than that.  Measured on the v5e (PR 29, call 6; the
same check with every prompt chunk started from a zeroed state): at -4.6
(decay 0.9 .. 0.999 over two standard deviations of heads, what ISSUE 29
named) the fault reads 0.022 against 0.018 without it, under the limit of
0.03: not seen; at -6.0 it reads 0.046.  -6.5 (softplus = 0.0015: a
median head keeps 1 - 1/670 of its state a token; 0.985 .. 0.99985 over
two standard deviations) is the value: see PERF.md, PR 29, for its
readings.  A decode step handed a zeroed state reads 0.40 at any of them.
The same mapping is applied to the reference's parameters; the program's
model is untouched (``_SeededDecay`` wraps it).
"""

from __future__ import annotations

from typing import Any, Dict

REFERENCE = "qwen3_next"

#: dt_bias = DT_SHIFT + DT_SCALE * z: softplus(-6.5) = 0.0015, and +-2
#: sigma gives 0.00015 .. 0.015 (the module doc says why)
DT_SHIFT, DT_SCALE = -6.5, 1.15


def program_config(hf: Dict[str, Any]):
    import jax.numpy as jnp

    from deepspeed_tpu.inference.v2.model_implementations. \
        ragged_qwen3_next import Qwen3NextConfig

    # (rope_scaling, use_sliding_window, mlp_only_layers: unset in the
    # published config; the reference refuses a configuration that sets one)
    return Qwen3NextConfig(
        vocab_size=hf["vocab_size"], hidden_size=hf["hidden_size"],
        num_hidden_layers=hf["num_hidden_layers"],
        num_attention_heads=hf["num_attention_heads"],
        num_key_value_heads=hf["num_key_value_heads"],
        head_dim=hf["head_dim"],
        partial_rotary_factor=float(hf["partial_rotary_factor"]),
        rope_theta=float(hf["rope_theta"]), rms_norm_eps=hf["rms_norm_eps"],
        max_position_embeddings=hf["max_position_embeddings"],
        full_attention_interval=hf["full_attention_interval"],
        linear_num_key_heads=hf["linear_num_key_heads"],
        linear_num_value_heads=hf["linear_num_value_heads"],
        linear_key_head_dim=hf["linear_key_head_dim"],
        linear_value_head_dim=hf["linear_value_head_dim"],
        linear_conv_kernel_dim=hf["linear_conv_kernel_dim"],
        num_experts=_router_width(hf), held_experts=hf["num_experts"],
        expert_start=int(hf.get("expert_start", 0)),
        num_experts_per_tok=hf["num_experts_per_tok"],
        moe_intermediate_size=hf["moe_intermediate_size"],
        shared_expert_intermediate_size=hf["shared_expert_intermediate_size"],
        norm_topk_prob=bool(hf["norm_topk_prob"]), dtype=jnp.bfloat16)


def _router_width(hf: Dict[str, Any]) -> int:
    return int(hf.get("router_experts", hf["num_experts"]))


def _seeded_decay(tree, A_log: str, dt_bias: str):
    """The mapping of the module doc on every DeltaNet layer of ``tree``
    (leaf names as given: the program's or the reference's)."""
    def walk(node):
        if isinstance(node, list):
            return [walk(v) for v in node]
        if not isinstance(node, dict):
            return node
        out = {k: walk(v) for k, v in node.items()}
        if dt_bias in out and A_log in out:
            z = out[dt_bias]
            out[dt_bias] = (DT_SHIFT + DT_SCALE * z.astype("float32")
                            ).astype(z.dtype)
        return out

    return walk(tree)


class _SeededDecay:
    """The served model with the seeded-decay mapping applied to the
    parameters on their way in (inside the step program: a few values a
    layer).  Everything else is the program's model."""

    def __init__(self, model):
        self._model = model

    def __getattr__(self, name):
        return getattr(self._model, name)

    def __call__(self, params, cache, batch, prefill_tile=None,
                 decode=False):
        return self._model(_seeded_decay(params, "A_log", "dt_bias"), cache,
                           batch, prefill_tile=prefill_tile, decode=decode)


def serve_model(hf: Dict[str, Any], block_size: int, mesh=None):
    from deepspeed_tpu.inference.v2.model_implementations. \
        ragged_qwen3_next import RaggedQwen3Next

    if mesh is not None:
        raise ValueError("RaggedQwen3Next serves one chip (TP = 1)")
    return _SeededDecay(RaggedQwen3Next(program_config(hf), block_size))


def serve_param_shapes(hf: Dict[str, Any]):
    from deepspeed_tpu.inference.v2.model_implementations. \
        ragged_qwen3_next import param_shapes

    return param_shapes(program_config(hf))


#: what a residual-writing kernel (a mixer's output projection, an
#: expert's down projection) is scaled by: the 1 / sqrt(2 L) of scaled-
#: residual initialisers (GPT-2, Megatron) at this configuration's L = 8
RESIDUAL_SCALE = 0.25


def init_std(path_names, shape) -> Any:
    """Seeded-weight scale per leaf: kernels N(0, 1/fan_in) (the stacked
    expert matrices and the convolution by their own fan-in), zero-centred
    norm scales 0, the gated norm's plain scale 1 (None), ``dt_bias``
    N(0, 1) and ``A_log`` 0 (see the module doc).

    **Embedding N(0, 1) and the residual-writing kernels (``out_proj``,
    ``o_proj``, the experts' ``w_down``, the shared expert's ``down_proj``)
    at ``RESIDUAL_SCALE`` x N(0, 1/fan_in)**, where the OLMoE family takes
    0.02 and 1.  With those the first layer's output is 30 times the
    embedding it is added to and every later layer rewrites the stream;
    such a network multiplies a relative perturbation of its residual
    stream by ~1.4 a layer (measured on the CPU at hidden 256: bf16 against
    float32, 0.8% after the first layer, 7.5% after the eighth; a DeltaNet
    mixer's output carries twice its input's relative error), so a bf16
    engine whose every layer is right reads 0.07-0.12 against the float32
    reference at eight layers (v5e, PR 29, calls 1-2; a float32 engine reads
    0.0006).  A trained network does not do that: its layers are small
    updates of the stream.  The scaled-residual initialiser is the standard
    way to seed one that behaves so, and the check then reads what bf16
    costs and not how far chaos carries it (0.012-0.016 at hidden 256)."""
    leaf, parent = path_names[-1], path_names[-2] if len(path_names) > 1 \
        else ""
    if leaf == "scale":
        return None if path_names[-3:-1] == ["linear_attn", "norm"] else 0.0
    if leaf == "embedding":
        return 1.0
    if leaf == "A_log":
        return 0.0
    if leaf == "dt_bias":
        return 1.0
    if leaf == "w_down":
        return RESIDUAL_SCALE * shape[1] ** -0.5
    if leaf in ("w_gate", "w_up"):
        return shape[1] ** -0.5
    if parent in ("out_proj", "o_proj", "down_proj"):
        return RESIDUAL_SCALE * shape[0] ** -0.5
    # (the convolution's [taps, channels] kernel: fan-in = taps)
    return shape[0] ** -0.5


def reference_params(params) -> Dict[str, Any]:
    """Program tree -> the plain reference's dict (no copy, no cast beyond
    the seeded-decay mapping's few values)."""
    n = sum(1 for k in params if k.startswith("layers_"))
    layers = []
    for i in range(n):
        lp = params[f"layers_{i}"]
        moe = lp["mlp"]
        se = moe["shared_expert"]
        layer = {
            "ln1": lp["input_layernorm"]["scale"],
            "ln2": lp["post_attention_layernorm"]["scale"],
            "router": moe["gate"]["wg"]["kernel"],
            "w_gate": moe["experts"]["w_gate"],
            "w_up": moe["experts"]["w_up"],
            "w_down": moe["experts"]["w_down"],
            "s_gate": se["gate_proj"]["kernel"],
            "s_up": se["up_proj"]["kernel"],
            "s_down": se["down_proj"]["kernel"],
            "s_sg": moe["shared_expert_gate"]["kernel"]}
        if "self_attn" in lp:
            att = lp["self_attn"]
            layer.update({
                "wq": att["q_proj"]["kernel"], "wk": att["k_proj"]["kernel"],
                "wv": att["v_proj"]["kernel"], "wo": att["o_proj"]["kernel"],
                "q_norm": att["q_norm"]["scale"],
                "k_norm": att["k_norm"]["scale"]})
        else:
            la = lp["linear_attn"]
            layer.update({
                "w_qkvz": la["in_proj_qkvz"]["kernel"],
                "w_ba": la["in_proj_ba"]["kernel"],
                "conv": la["conv1d"]["kernel"], "A_log": la["A_log"],
                "dt_bias": la["dt_bias"], "gnorm": la["norm"]["scale"],
                "wo": la["out_proj"]["kernel"]})
        layers.append(layer)
    return _seeded_decay(
        {"embed": params["embed_tokens"]["embedding"], "layers": layers,
         "norm": params["norm"]["scale"],
         "lm_head": params["lm_head"]["kernel"]}, "A_log", "dt_bias")


def shapes(hf: Dict[str, Any]) -> Dict[str, int]:
    """Shape facts for ``lib/costs.py``, ``lib/costs_moe.py`` and
    ``lib/costs_gdn.py``.  A state-bearing family gives, beside
    ``kv_bytes_per_token`` (over its attention layers only), what ONE
    sequence holds whatever its length: ``state_bytes_per_seq`` (the
    float32 recurrent matrices and the convolution tails of every DeltaNet
    layer), and how many slots there are (``state_slots``, when the
    configuration has a ``serve`` block).  ``experts`` is what is HELD
    here, ``router_width`` the published count.  ``matmul_params`` counts
    what one token multiplies by on this chip on average: the mixers, the
    router, the shared expert, ``experts_per_token x held / router_width``
    routed experts and the lm_head."""
    h, v = hf["hidden_size"], hf["vocab_size"]
    hq, hkv, d = hf["num_attention_heads"], hf["num_key_value_heads"], \
        hf["head_dim"]
    hk, hv = hf["linear_num_key_heads"], hf["linear_num_value_heads"]
    dk, dv = hf["linear_key_head_dim"], hf["linear_value_head_dim"]
    e, er, k = hf["num_experts"], _router_width(hf), \
        hf["num_experts_per_tok"]
    f, fs = hf["moe_intermediate_size"], \
        hf["shared_expert_intermediate_size"]
    layers = hf["num_hidden_layers"]
    attn_layers = sum((i + 1) % hf["full_attention_interval"] == 0
                      for i in range(layers))
    gdn_layers = layers - attn_layers
    conv_dim = 2 * hk * dk + hv * dv
    taps = hf["linear_conv_kernel_dim"]
    attn = h * 2 * hq * d + 2 * h * hkv * d + hq * d * h
    gdn = h * (conv_dim + hv * dv) + h * 2 * hv + hv * dv * h
    gdn_small = taps * conv_dim + 2 * hv + dv
    moe_fixed = h * er + 3 * h * fs + h
    out = {"layers": layers, "hidden": h, "q_heads": hq, "kv_heads": hkv,
           "head_dim": d, "vocab": v,
           "attn_layers": attn_layers, "gdn_layers": gdn_layers,
           "gdn_value_heads": hv, "gdn_key_dim": dk, "gdn_value_dim": dv,
           "experts": e, "router_width": er, "experts_per_token": k,
           "expert_width": f,
           "matmul_params": attn_layers * attn + gdn_layers * gdn
           + layers * (moe_fixed + k * e * 3 * h * f // er) + h * v,
           "total_params": attn_layers * (attn + 2 * d)
           + gdn_layers * (gdn + gdn_small)
           + layers * (moe_fixed + e * 3 * h * f + 2 * h) + 2 * h * v + h,
           "kv_bytes_per_token": 2 * attn_layers * hkv * d * 2,
           "state_bytes_per_seq": gdn_layers * (hv * dk * dv * 4
                                                + (taps - 1) * conv_dim * 2)}
    if "serve" in hf:
        out["state_slots"] = int(hf["serve"]["max_ragged_sequence_count"])
    return out
