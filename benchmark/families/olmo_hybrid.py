"""Olmo-Hybrid family adapter: from the published ``config.json`` keys
(``model_type: olmo_hybrid``) to the program's model object
(``RaggedOlmoHybrid``), to the plain reference's parameter dict, and to the
shape facts the FLOP/byte functions need.  The only file that knows both
namings.

**The cut is in depth alone**: every head, every width and the whole
vocabulary are held here, so no share has to be told to program or
reference (``layer_types`` in the configuration file is cut with
``num_hidden_layers``).

**Seeded decay.**  As the Qwen3-Next family does it and for its measured
reasons (``families/qwen3_next.py``, whose mapping this file applies):
``dt_bias = -6.5 + 1.15 z`` from the seeded N(0, 1) leaf and ``A_log = 0``,
so that a median head remembers ~670 tokens and a state or a convolution
tail dropped at the check's chunk boundary is seen 512 tokens later.

**Seeded post-norm weights.**  In a post-norm block what a sub-layer adds
to the stream is ``RMS(f(h)) * w``: its size is the post norm's weight, not
the scale of the kernel before it (the norm divides that scale out).  So the
scaled-residual 1 / sqrt(2 L) = 1/4 at L = 8, which the pre-norm families
put on their residual-writing kernels, goes on the two post norms' seeded
weights here (``POST_NORM``), and every kernel is N(0, 1/fan_in).  With an
embedding of N(0, 1) each of the 16 sub-layers then adds a vector of RMS 1/4
to a stream of RMS ~1: small updates, as a trained network's are, so the
check reads what bf16 costs and not how far a chaotic stack carries one
rounding (the argument and the measurements are in
``families/qwen3_next.py::init_std``).  The runner seeds a leaf as N(0,
std^2), ones or zeros; a CONSTANT 1/4 is none of those, so the served model
and the reference both read ``POST_NORM * w`` from leaves seeded as ones
(``_seeded`` below, the same few values a layer as the decay's mapping).
"""

from __future__ import annotations

from typing import Any, Dict

from benchmark.families.qwen3_next import DT_SCALE, DT_SHIFT, _seeded_decay  # noqa: F401

REFERENCE = "olmo_hybrid"

#: what a post norm's seeded weight is: 1 / sqrt(2 L) at L = 8 (module doc)
POST_NORM = 0.25
_POST_NORMS = ("post_attention_layernorm", "post_feedforward_layernorm")


def program_config(hf: Dict[str, Any]):
    import jax.numpy as jnp

    from deepspeed_tpu.inference.v2.model_implementations. \
        ragged_olmo_hybrid import OlmoHybridConfig

    return OlmoHybridConfig(
        vocab_size=hf["vocab_size"], hidden_size=hf["hidden_size"],
        intermediate_size=hf["intermediate_size"],
        num_hidden_layers=hf["num_hidden_layers"],
        num_attention_heads=hf["num_attention_heads"],
        num_key_value_heads=hf["num_key_value_heads"],
        layer_types=tuple(hf["layer_types"]),
        linear_num_key_heads=hf["linear_num_key_heads"],
        linear_num_value_heads=hf["linear_num_value_heads"],
        linear_key_head_dim=hf["linear_key_head_dim"],
        linear_value_head_dim=hf["linear_value_head_dim"],
        linear_conv_kernel_dim=hf["linear_conv_kernel_dim"],
        linear_allow_neg_eigval=bool(hf["linear_allow_neg_eigval"]),
        rms_norm_eps=hf["rms_norm_eps"],
        rope_theta=(hf.get("rope_parameters") or {}).get("rope_theta"),
        max_position_embeddings=hf["max_position_embeddings"],
        tie_word_embeddings=bool(hf["tie_word_embeddings"]),
        dtype=jnp.bfloat16)


def _seeded(params):
    """The program's tree with the seeded-decay mapping on every DeltaNet
    layer and ``POST_NORM`` on every post norm's weight (module doc)."""
    out = _seeded_decay(params, "A_log", "dt_bias")
    for name, lp in out.items():
        if name.startswith("layers_"):
            for norm in _POST_NORMS:
                w = lp[norm]["scale"]
                lp[norm] = {"scale": (POST_NORM * w.astype("float32")
                                      ).astype(w.dtype)}
    return out


class _Seeded:
    """The served model with ``_seeded`` applied to the parameters on their
    way in (inside the step program: a few values a layer).  Everything
    else is the program's model."""

    def __init__(self, model):
        self._model = model

    def __getattr__(self, name):
        return getattr(self._model, name)

    def __call__(self, params, cache, batch, prefill_tile=None,
                 decode=False):
        return self._model(_seeded(params), cache, batch,
                           prefill_tile=prefill_tile, decode=decode)


def serve_model(hf: Dict[str, Any], block_size: int, mesh=None):
    from deepspeed_tpu.inference.v2.model_implementations. \
        ragged_olmo_hybrid import RaggedOlmoHybrid

    return _Seeded(RaggedOlmoHybrid(program_config(hf), block_size,
                                    mesh=mesh))


def serve_param_shapes(hf: Dict[str, Any]):
    from deepspeed_tpu.inference.v2.model_implementations. \
        ragged_olmo_hybrid import param_shapes

    return param_shapes(program_config(hf))


def init_std(path_names, shape) -> Any:
    """Seeded-weight scale per leaf: kernels N(0, 1/fan_in) (the
    convolution by its taps), the embedding N(0, 1), every norm weight 1
    (None; the post norms' become ``POST_NORM`` on the way in), ``dt_bias``
    N(0, 1) and ``A_log`` 0 (module doc)."""
    leaf = path_names[-1]
    if leaf == "scale":
        return None
    if leaf in ("embedding", "dt_bias"):
        return 1.0
    if leaf == "A_log":
        return 0.0
    # (the convolution's [taps, channels] kernel: fan-in = taps)
    return shape[0] ** -0.5


def reference_params(params) -> Dict[str, Any]:
    """Program tree -> the plain reference's dict (no copy, no cast beyond
    the two mappings' few values)."""
    params = _seeded(params)
    n = sum(1 for k in params if k.startswith("layers_"))
    layers = []
    for i in range(n):
        lp = params[f"layers_{i}"]
        layer = {"post_attn": lp["post_attention_layernorm"]["scale"],
                 "post_ff": lp["post_feedforward_layernorm"]["scale"],
                 "w_gate": lp["mlp"]["gate_proj"]["kernel"],
                 "w_up": lp["mlp"]["up_proj"]["kernel"],
                 "w_down": lp["mlp"]["down_proj"]["kernel"]}
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
    return {"embed": params["embed_tokens"]["embedding"], "layers": layers,
            "norm": params["norm"]["scale"],
            "lm_head": params["lm_head"]["kernel"]}


def shapes(hf: Dict[str, Any]) -> Dict[str, int]:
    """Shape facts for ``lib/costs.py``, ``lib/costs_gdn.py``,
    ``lib/costs_paged.py`` and ``lib/costs_hybrid.py``.  As the other
    state-bearing families: ``kv_bytes_per_token`` over the attention
    layers only, ``state_bytes_per_seq`` what ONE sequence holds whatever
    its length AS THE MATHEMATICS COUNTS IT (the float32 matrices and the
    bf16 convolution tails of every DeltaNet layer: lanes a layout pads are
    not in it, so they show as share lost), ``state_slots`` from the
    ``serve`` block.  ``matmul_params`` is what one token multiplies by
    (the head included), ``head_params`` the head alone,
    ``gdn_conv_channels`` / ``gdn_conv_taps`` the convolution's."""
    h, v, f = hf["hidden_size"], hf["vocab_size"], hf["intermediate_size"]
    hq, hkv = hf["num_attention_heads"], hf["num_key_value_heads"]
    d = h // hq
    hk, hv = hf["linear_num_key_heads"], hf["linear_num_value_heads"]
    dk, dv = hf["linear_key_head_dim"], hf["linear_value_head_dim"]
    taps = hf["linear_conv_kernel_dim"]
    layers = hf["num_hidden_layers"]
    attn_layers = sum(k == "full_attention" for k in hf["layer_types"])
    gdn_layers = layers - attn_layers
    conv_dim = 2 * hk * dk + hv * dv
    attn = h * hq * d + 2 * h * hkv * d + hq * d * h
    gdn = h * (conv_dim + hv * dv) + h * 2 * hv + hv * dv * h
    gdn_small = taps * conv_dim + 2 * hv + dv
    ffn = 3 * h * f
    out = {"layers": layers, "hidden": h, "q_heads": hq, "kv_heads": hkv,
           "head_dim": d, "vocab": v,
           "attn_layers": attn_layers, "gdn_layers": gdn_layers,
           "gdn_value_heads": hv, "gdn_key_dim": dk, "gdn_value_dim": dv,
           "gdn_conv_channels": conv_dim, "gdn_conv_taps": taps,
           "head_params": h * v,
           "matmul_params": attn_layers * attn + gdn_layers * gdn
           + layers * ffn + h * v,
           "total_params": attn_layers * (attn + hq * d + hkv * d)
           + gdn_layers * (gdn + gdn_small)
           + layers * (ffn + 2 * h) + 2 * h * v + h,
           "kv_bytes_per_token": 2 * attn_layers * hkv * d * 2,
           "state_bytes_per_seq": gdn_layers * (hv * dk * dv * 4
                                                + (taps - 1) * conv_dim * 2)}
    if "serve" in hf:
        out["state_slots"] = int(hf["serve"]["max_ragged_sequence_count"])
    return out
