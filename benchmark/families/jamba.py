"""Jamba family adapter (the dense sibling, ``num_experts`` 1): from the
published ``config.json`` keys (``model_type: jamba``, ai21labs/
AI21-Jamba2-3B) to the program's model object (``RaggedJamba``), to the
plain reference's parameter dict, and to the shape facts the FLOP/byte
functions need.  The only file that knows both namings.

**The state is the cache.**  ``shapes`` fills ``kv_bytes_per_token`` over
the ATTENTION layers alone (2 layers x 2 x 1 head x 128 x 2 B = 1,024) and
``state_bytes_per_seq`` with what one sequence holds whatever its length:
26 Mamba layers x (16 x 5120 x 4 B of float32 scan state + 3 x 5120 x 2 B of
convolution tail) = 9,318,400 B; ``state_slots`` when the configuration has
a ``serve`` block; ``ssm_layers`` / ``ssm_channels`` / ``ssm_state`` are
what ``lib/costs_ssm.py`` reads.

**Seeded weights** (the runner makes every leaf N(0, std^2), ones or zeros
from the seed; what the published initialiser would give cannot be told
from logits, see below).  Kernels N(0, 1/fan_in) (the convolution's
``[taps, channels]`` kernel by its taps), norm weights 1, the embedding,
which is also the head, N(0, 0.7^2) as the LFM2 family's (a token's logit
for its own id is what the check divides by), the SwiGLU's ``down_proj``
at 1 / sqrt(2 L) of that (the scaled-residual scale of the Qwen3-Next and
LFM2 families, L = 28).  The two mixers' output projections are NOT scaled
down but up (``MAMBA_OUT``, ``ATTN_OUT``): with seeded weights a softmax
over a thousand keys averages the values away and a scan whose state is a
few percent of ``D x`` hardly reaches ``y``, so at the scaled-residual scale
either mixer writes a hundredth of what the SwiGLU writes and a program
that dropped the state, the carry or the bias would pass the check.

**Seeded decay** (``_seeded_ssm``, applied to the served model's parameters
on their way into the step program and to the reference's alike, as
``families/qwen3_next.py::_seeded_decay``): ``A_log = log(1..N)`` along the
state axis (the published initialiser, ``A = -(1..16)``); ``b_dt =
DT_SHIFT + DT_SCALE z`` from the seeded N(0, 1) leaf ``z`` (so ``dt =
softplus(dt_r W_dt + b_dt)`` is log-normal about ``exp(DT_SHIFT)``, the
token's own term another factor ``e^{+-1}``: the published range is
log-uniform in 0.001 .. 0.1); ``D = D_SCALE`` (published: 1).  A channel's
decay a token is ``exp(-dt n)``, ``n = 1..16``: at ``dt`` 0.0025 state 1
keeps 1 - 1/400 and state 16 1 - 1/25, and over the channels' spread of
``dt`` the memory runs from a few tokens to a few thousand.  The check
compares logits 512 tokens behind the boundary of its two prefill chunks,
so a good share of the state has to remember further back than that, or a
carry dropped there passes (PR 29 found exactly that with the published
initialiser of the Gated DeltaNet).  With ``D`` at 1 the scan is a tenth of
``y`` (its variance is ``1.69 dt`` of ``x``'s: 0.004 at this ``dt``), so
``D_SCALE`` puts the skip term beside the scan and not over it.  The
readings that placed these are in PERF.md (PR 46) and in the
configuration's ``assumed.weights``.
"""

from __future__ import annotations

from typing import Any, Dict

REFERENCE = "jamba"

#: the embedding (and with it the tied head): the LFM2 family's
EMBED_STD = 0.7
#: b_dt = DT_SHIFT + DT_SCALE * z: softplus(-6.0) = 0.0025 (the module doc)
DT_SHIFT, DT_SCALE = -6.0, 1.0
#: D, the skip term's weight (published initialiser: 1; the module doc)
D_SCALE = 0.1
#: the convolution's bias: N(0, CONV_BIAS_STD^2) beside a unit pre-activation
CONV_BIAS_STD = 0.5
#: what the mixers' output projections are scaled by beside N(0, 1/fan_in)
MAMBA_OUT, ATTN_OUT = 2.0, 4.0
#: what the SwiGLU's down_proj is scaled by: the 1 / sqrt(2 L) of
#: scaled-residual initialisers at this configuration's L = 28
RESIDUAL_SCALE = 56 ** -0.5


def _layers(hf: Dict[str, Any]):
    n, period, offset = int(hf["num_hidden_layers"]), \
        int(hf["attn_layer_period"]), int(hf["attn_layer_offset"])
    attn = sum(i % period == offset for i in range(n))
    return n, attn, n - attn


def program_config(hf: Dict[str, Any]):
    import jax.numpy as jnp

    from deepspeed_tpu.inference.v2.model_implementations.ragged_jamba \
        import JambaConfig

    return JambaConfig(
        vocab_size=hf["vocab_size"], hidden_size=hf["hidden_size"],
        intermediate_size=hf["intermediate_size"],
        num_hidden_layers=hf["num_hidden_layers"],
        num_attention_heads=hf["num_attention_heads"],
        num_key_value_heads=hf["num_key_value_heads"],
        attn_layer_period=hf["attn_layer_period"],
        attn_layer_offset=hf["attn_layer_offset"],
        mamba_expand=hf["mamba_expand"], mamba_d_state=hf["mamba_d_state"],
        mamba_d_conv=hf["mamba_d_conv"], mamba_dt_rank=hf["mamba_dt_rank"],
        mamba_conv_bias=bool(hf["mamba_conv_bias"]),
        mamba_proj_bias=bool(hf["mamba_proj_bias"]),
        num_experts=hf["num_experts"],
        num_experts_per_tok=hf["num_experts_per_tok"],
        rms_norm_eps=hf["rms_norm_eps"],
        max_position_embeddings=hf["max_position_embeddings"],
        tie_word_embeddings=bool(hf["tie_word_embeddings"]),
        sliding_window=hf.get("sliding_window"), dtype=jnp.bfloat16)


def _seeded_ssm(tree):
    """The mapping of the module doc on every Mamba layer of the program's
    parameter tree (``A_log`` is ``[N, Di]`` there)."""
    import jax.numpy as jnp

    def walk(node):
        if not isinstance(node, dict):
            return node
        out = {k: walk(v) for k, v in node.items()}
        if "A_log" in out and "D" in out:
            a, z = out["A_log"], out["dt_proj"]["bias"]
            ramp = jnp.log(jnp.arange(1, a.shape[0] + 1, dtype=jnp.float32))
            out["A_log"] = jnp.broadcast_to(ramp[:, None],
                                            a.shape).astype(a.dtype)
            out["D"] = jnp.full_like(out["D"], D_SCALE)
            out["dt_proj"] = {**out["dt_proj"], "bias": (
                DT_SHIFT + DT_SCALE * z.astype(jnp.float32)).astype(z.dtype)}
        return out

    return walk(tree)


class _SeededSsm:
    """The served model with the seeded-decay mapping applied to the
    parameters on their way in (inside the step program: three small leaves
    a layer).  Everything else is the program's model."""

    def __init__(self, model):
        self._model = model

    def __getattr__(self, name):
        return getattr(self._model, name)

    def __call__(self, params, cache, batch, prefill_tile=None,
                 decode=False):
        return self._model(_seeded_ssm(params), cache, batch,
                           prefill_tile=prefill_tile, decode=decode)


def serve_model(hf: Dict[str, Any], block_size: int, mesh=None):
    from deepspeed_tpu.inference.v2.model_implementations.ragged_jamba \
        import RaggedJamba

    if mesh is not None:
        raise ValueError("RaggedJamba serves one chip (TP = 1)")
    return _SeededSsm(RaggedJamba(program_config(hf), block_size))


def serve_param_shapes(hf: Dict[str, Any]):
    from deepspeed_tpu.inference.v2.model_implementations.ragged_jamba \
        import param_shapes

    return param_shapes(program_config(hf))


def init_std(path_names, shape) -> Any:
    """Seeded-weight scale per leaf (the module doc).  ``A_log`` and ``D``
    are made zero and one and then replaced by ``_seeded_ssm``; ``dt_proj``'s
    bias is the N(0, 1) leaf it shifts."""
    leaf, parent = path_names[-1], path_names[-2] if len(path_names) > 1 \
        else ""
    if leaf == "scale" or leaf == "D":
        return None
    if leaf == "embedding":
        return EMBED_STD
    if leaf == "A_log":
        return 0.0
    if leaf == "bias":
        return 1.0 if parent == "dt_proj" else CONV_BIAS_STD
    if parent == "out_proj":
        return MAMBA_OUT * shape[0] ** -0.5
    if parent == "o_proj":
        return ATTN_OUT * shape[0] ** -0.5
    if parent == "down_proj":
        return RESIDUAL_SCALE * shape[0] ** -0.5
    # (the convolution's [taps, channels] kernel: fan-in = taps)
    return shape[0] ** -0.5


def reference_params(params) -> Dict[str, Any]:
    """Program tree -> the plain reference's dict (no copy beyond the
    seeded mapping's three small leaves and ``A_log``'s transpose to the
    published ``[Di, N]``)."""
    params = _seeded_ssm(params)
    n = sum(1 for k in params if k.startswith("layers_"))
    layers = []
    for i in range(n):
        lp = params[f"layers_{i}"]
        mlp = lp["mlp"]
        layer = {"ln1": lp["input_layernorm"]["scale"],
                 "ln2": lp["pre_ff_layernorm"]["scale"],
                 "gate": mlp["gate_proj"]["kernel"],
                 "up": mlp["up_proj"]["kernel"],
                 "down": mlp["down_proj"]["kernel"]}
        if "mamba" in lp:
            mb = lp["mamba"]
            layer.update({
                "w_in": mb["in_proj"]["kernel"],
                "taps": mb["conv1d"]["kernel"],
                "conv_bias": mb["conv1d"]["bias"],
                "w_x": mb["x_proj"]["kernel"],
                "w_dt": mb["dt_proj"]["kernel"],
                "b_dt": mb["dt_proj"]["bias"],
                "A_log": mb["A_log"].T, "D": mb["D"],
                "g_dt": mb["dt_layernorm"]["scale"],
                "g_b": mb["b_layernorm"]["scale"],
                "g_c": mb["c_layernorm"]["scale"],
                "w_out": mb["out_proj"]["kernel"]})
        else:
            att = lp["self_attn"]
            layer.update({
                "wq": att["q_proj"]["kernel"], "wk": att["k_proj"]["kernel"],
                "wv": att["v_proj"]["kernel"], "wo": att["o_proj"]["kernel"]})
        layers.append(layer)
    return {"embed": params["embed_tokens"]["embedding"], "layers": layers,
            "norm": params["final_layernorm"]["scale"]}


def shapes(hf: Dict[str, Any]) -> Dict[str, int]:
    """Shape facts for ``lib/costs.py``, ``lib/costs_paged.py`` and
    ``lib/costs_ssm.py`` (the module doc says how a family whose state is
    its cache fills them).  ``matmul_params`` counts what one token
    multiplies by: the mixers' projections, every layer's SwiGLU and the
    head (the tied embedding, once)."""
    h, v, f = hf["hidden_size"], hf["vocab_size"], hf["intermediate_size"]
    hq, hkv = hf["num_attention_heads"], hf["num_key_value_heads"]
    d = h // hq
    di, n, r, taps = hf["mamba_expand"] * h, hf["mamba_d_state"], \
        hf["mamba_dt_rank"], hf["mamba_d_conv"]
    layers, attn_layers, ssm_layers = _layers(hf)
    attn = 2 * h * hq * d + 2 * h * hkv * d
    mamba = h * 2 * di + di * (r + 2 * n) + r * di + di * h
    mamba_small = taps * di + di + di + n * di + di + r + 2 * n
    out = {"layers": layers, "hidden": h, "q_heads": hq, "kv_heads": hkv,
           "head_dim": d, "vocab": v,
           "attn_layers": attn_layers, "ssm_layers": ssm_layers,
           "ssm_channels": di, "ssm_state": n, "ssm_dt_rank": r,
           "conv_taps": taps,
           "matmul_params": attn_layers * attn + ssm_layers * mamba
           + layers * 3 * h * f + h * v,
           "total_params": attn_layers * attn
           + ssm_layers * (mamba + mamba_small)
           + layers * (3 * h * f + 2 * h) + h * v + h,
           "kv_bytes_per_token": 2 * attn_layers * hkv * d * 2,
           "state_bytes_per_seq": ssm_layers * (n * di * 4
                                                + (taps - 1) * di * 2)}
    if "serve" in hf:
        out["state_slots"] = int(hf["serve"]["max_ragged_sequence_count"])
    return out
