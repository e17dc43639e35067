"""Granite-4.0-H family adapter: from the published ``config.json`` keys
(``model_type: granitemoehybrid``, ibm-granite/granite-4.0-h-small) to the
program's model object (``RaggedGraniteMoeHybrid``), to the plain
reference's parameter dict, and to the shape facts the FLOP/byte functions
need.  The only file that knows both namings.

**A share of the experts.**  ``num_local_experts`` in the configuration is
what is HELD here; ``router_experts`` (the published count) is the router's
width and ``expert_start`` the first held expert, as the Qwen3-Next
configuration has them.

**The state is the cache.**  ``shapes`` fills ``kv_bytes_per_token`` over
the ATTENTION layers alone (1 layer x 2 x 8 heads x 128 x 2 B = 4,096) and
``state_bytes_per_seq`` with what one sequence holds whatever its length: 9
Mamba-2 layers x (128 x 8192 x 4 B of float32 state + 3 x 8448 x 2 B of
convolution tail) = 38,204,928 B; ``state_slots`` when the configuration
has a ``serve`` block; ``ssd_layers`` / ``ssd_heads`` / ``ssd_head_dim`` /
``ssd_state`` are what ``lib/costs_ssd.py`` reads.

**Seeded weights** (the runner makes every leaf N(0, std^2), ones or zeros
from the seed).  The published multipliers shape the choice:
``residual_multiplier`` 0.22 IS the scaled-residual factor 1 / sqrt(2 L) at
this configuration's L = 10, so every branch keeps N(0, 1/fan_in), a unit
output, and is not scaled down.  The embedding, which is also the head, is
N(0, EMBED_STD^2) with ``12 x EMBED_STD`` = 0.24 well under the layers'
summed updates: a token's logit for its own id (what the check divides by)
is then two to four times the largest other logit and a mechanism that moves
a tenth of the stream is seen.  ``q_proj`` and ``k_proj`` at ``QK_SCALE``:
the scores are ``q . k / 128`` (muP), a hundredth of a unit projection's
``q . k / sqrt(128)`` would be level, a softmax over 1,500 level scores
averages the values away and the scale itself could not be told;
``o_proj`` at ``ATTN_OUT``.  The router at ``ROUTER_SCALE`` (logits of
spread 2: the ten chosen hold ~0.6 of a softmax over all 72).

**What the routed experts' scale trades** (``EXPERT_OUT``; v5e, PR 59,
calls 1-3): between the bf16 engine and the float32 reference the tenth and
eleventh of a token's 72 router logits change places in one (token, layer)
of ten or so, and what such a flip writes stays in the Mamba-2 states and
the keys of every later token.  The clean program read 0.033-0.073 at
``EXPERT_OUT`` 8 (``SHARED_OUT`` 2.5, ``ROUTER_SCALE`` 1: call 1), and with
the values below 0.009 / 0.062 / 0.026 at 4, 0.005 / 0.032 / 0.018 at 3,
0.005 / 0.015 / 0.006 at 2 (call 2, three seeds a value): heavy-tailed, and
growing faster than the scale; at 1 it reads 0.0034-0.0043 over seven seeds
(call 3).  The one fault that needs the routed part large, a softmax over
all 72 logits with the ten not renormalised, read 0.016 / 0.027 at 1, 0.053
/ 0.029 / 0.043 at 2 and 0.061 / 0.051 / 0.064 at 3: three to four times
the clean reading's tail at any scale, so no scale puts the clean program
safely under 0.03 and that fault safely over it.  ``EXPERT_OUT`` is 1, a
unit expert: the clean program comes first (one run with ``correct`` false
refuses a PR), and ``benchmark/tools/calls/pr59_faults.py`` prints that
fault as a reading (the float32 CPU test sees it at 100 times its limit).
The shared expert's ``down_proj`` is a unit kernel too (``SHARED_OUT``: at
2.5 it doubled the clean reading of a CPU probe at hidden 1,024 and with it
the flips; dropped, it still reads 0.25-0.27 at 1).

**Seeded decay** (``_seeded_ssd``, applied to the served model's parameters
on their way into the step program and to the reference's alike, as
``families/jamba.py::_seeded_ssm``): ``A_log`` a ramp over the heads,
``log A`` level between ``log A_LO`` and ``log A_HI`` (the published
initialiser draws A uniform in 1-16); ``dt_bias = DT_SHIFT + DT_SCALE z``
from the seeded N(0, 1) leaf ``z`` (``dt = softplus(dt_raw + dt_bias)`` is
log-normal about ``exp(DT_SHIFT)``, the token's own ``dt_raw`` another
factor ``e^{+-1}``; published: log-uniform 0.001-0.1); ``D = D_SCALE``
(published: 1).  A head forgets at ``exp(-dt A)`` a token: its memory ``1 /
(dt A)`` runs from a few tokens (A = 16, a large dt) to a few thousand (A =
1/8), so that a good share of the state at the check's last positions was
written more than 512 tokens earlier, before the boundary of its two
prefill chunks: a carry dropped there, or a slot another sequence left,
has to show (0.086-0.102 and 0.044-0.057, call 3).  ``D`` at 0.5 puts the
skip term beside the recurrence's part of ``Y`` (dropped: 0.088-0.091).  The
readings that placed these are in PERF.md (PR 59) and in the
configuration's ``assumed.weights``.
"""

from __future__ import annotations

import math
from typing import Any, Dict

REFERENCE = "granite_moe_hybrid"

#: the embedding (and with it the tied head); x embedding_multiplier 12
EMBED_STD = 0.02
#: q_proj and k_proj beside N(0, 1/fan_in): scores q . k / 128 of unit size
QK_SCALE = 4.75
#: the router beside N(0, 1/fan_in): its logits' spread (the module doc)
ROUTER_SCALE = 2.0
#: what the residual-writing kernels are scaled by beside N(0, 1/fan_in)
MAMBA_OUT, ATTN_OUT, EXPERT_OUT, SHARED_OUT = 1.0, 2.0, 1.0, 1.0
#: dt_bias = DT_SHIFT + DT_SCALE * z: softplus(-6.5) = 0.0015
DT_SHIFT, DT_SCALE = -6.5, 1.0
#: A = exp(A_log): a ramp over the heads, level in log A
A_LO, A_HI = 0.125, 16.0
#: D, the skip term's weight (published initialiser: 1)
D_SCALE = 0.5
#: the convolution's bias: N(0, CONV_BIAS_STD^2) beside a unit pre-activation
CONV_BIAS_STD = 0.5


def _router_width(hf: Dict[str, Any]) -> int:
    return int(hf.get("router_experts", hf["num_local_experts"]))


def program_config(hf: Dict[str, Any]):
    import jax.numpy as jnp

    from deepspeed_tpu.inference.v2.model_implementations. \
        ragged_granite_moe_hybrid import GraniteMoeHybridConfig

    return GraniteMoeHybridConfig(
        vocab_size=hf["vocab_size"], hidden_size=hf["hidden_size"],
        intermediate_size=hf["intermediate_size"],
        shared_intermediate_size=hf["shared_intermediate_size"],
        num_hidden_layers=hf["num_hidden_layers"],
        layer_types=tuple(hf["layer_types"]),
        num_attention_heads=hf["num_attention_heads"],
        num_key_value_heads=hf["num_key_value_heads"],
        mamba_n_heads=hf["mamba_n_heads"], mamba_d_head=hf["mamba_d_head"],
        mamba_d_state=hf["mamba_d_state"],
        mamba_n_groups=hf["mamba_n_groups"],
        mamba_d_conv=hf["mamba_d_conv"], mamba_expand=hf["mamba_expand"],
        mamba_conv_bias=bool(hf["mamba_conv_bias"]),
        mamba_proj_bias=bool(hf["mamba_proj_bias"]),
        attention_bias=bool(hf["attention_bias"]),
        position_embedding_type=hf["position_embedding_type"],
        num_local_experts=_router_width(hf),
        held_experts=hf["num_local_experts"],
        expert_start=int(hf.get("expert_start", 0)),
        num_experts_per_tok=hf["num_experts_per_tok"],
        embedding_multiplier=float(hf["embedding_multiplier"]),
        residual_multiplier=float(hf["residual_multiplier"]),
        attention_multiplier=float(hf["attention_multiplier"]),
        logits_scaling=float(hf["logits_scaling"]),
        rms_norm_eps=hf["rms_norm_eps"],
        max_position_embeddings=hf["max_position_embeddings"],
        tie_word_embeddings=bool(hf["tie_word_embeddings"]),
        dtype=jnp.bfloat16)


def _seeded_ssd(tree):
    """The mapping of the module doc on every Mamba-2 layer of the
    program's parameter tree (``A_log``, ``dt_bias``, ``D`` are ``[H]``)."""
    import jax.numpy as jnp

    def walk(node):
        if not isinstance(node, dict):
            return node
        out = {k: walk(v) for k, v in node.items()}
        if "A_log" in out and "dt_bias" in out:
            a, z = out["A_log"], out["dt_bias"]
            ramp = jnp.linspace(math.log(A_LO), math.log(A_HI), a.shape[0],
                                dtype=jnp.float32)
            out["A_log"] = ramp.astype(a.dtype)
            out["D"] = jnp.full_like(out["D"], D_SCALE)
            out["dt_bias"] = (DT_SHIFT + DT_SCALE * z.astype(jnp.float32)
                              ).astype(z.dtype)
        return out

    return walk(tree)


class _SeededSsd:
    """The served model with the seeded-decay mapping applied to the
    parameters on their way in (inside the step program: three ``[H]``
    leaves a layer).  Everything else is the program's model."""

    def __init__(self, model):
        self._model = model

    def __getattr__(self, name):
        return getattr(self._model, name)

    def __call__(self, params, cache, batch, prefill_tile=None,
                 decode=False):
        return self._model(_seeded_ssd(params), cache, batch,
                           prefill_tile=prefill_tile, decode=decode)


def serve_model(hf: Dict[str, Any], block_size: int, mesh=None):
    from deepspeed_tpu.inference.v2.model_implementations. \
        ragged_granite_moe_hybrid import RaggedGraniteMoeHybrid

    if mesh is not None:
        raise ValueError("RaggedGraniteMoeHybrid serves one chip (TP = 1)")
    return _SeededSsd(RaggedGraniteMoeHybrid(program_config(hf), block_size))


def serve_param_shapes(hf: Dict[str, Any]):
    from deepspeed_tpu.inference.v2.model_implementations. \
        ragged_granite_moe_hybrid import param_shapes

    return param_shapes(program_config(hf))


def init_std(path_names, shape) -> Any:
    """Seeded-weight scale per leaf (the module doc).  ``A_log`` and ``D``
    are made zero and one and then replaced by ``_seeded_ssd``; ``dt_bias``
    is the N(0, 1) leaf it shifts."""
    leaf, parent = path_names[-1], path_names[-2] if len(path_names) > 1 \
        else ""
    if leaf == "scale" or leaf == "D":
        return None
    if leaf == "embedding":
        return EMBED_STD
    if leaf == "A_log":
        return 0.0
    if leaf == "dt_bias":
        return 1.0
    if leaf == "bias":
        return CONV_BIAS_STD
    if leaf == "w_down":
        return EXPERT_OUT * shape[1] ** -0.5
    if leaf in ("w_gate", "w_up"):
        return shape[1] ** -0.5
    if parent in ("q_proj", "k_proj"):
        return QK_SCALE * shape[0] ** -0.5
    if parent == "out_proj":
        return MAMBA_OUT * shape[0] ** -0.5
    if parent == "o_proj":
        return ATTN_OUT * shape[0] ** -0.5
    if parent == "down_proj":
        return SHARED_OUT * shape[0] ** -0.5
    if parent == "wg":
        return ROUTER_SCALE * shape[0] ** -0.5
    # (the convolution's [taps, channels] kernel: fan-in = taps)
    return shape[0] ** -0.5


def reference_params(params) -> Dict[str, Any]:
    """Program tree -> the plain reference's dict (no copy beyond the
    seeded mapping's three small leaves a layer)."""
    params = _seeded_ssd(params)
    n = sum(1 for k in params if k.startswith("layers_"))
    layers = []
    for i in range(n):
        lp = params[f"layers_{i}"]
        moe = lp["block_sparse_moe"]
        se = moe["shared_expert"]
        layer = {"ln1": lp["input_layernorm"]["scale"],
                 "ln2": lp["post_attention_layernorm"]["scale"],
                 "router": moe["gate"]["wg"]["kernel"],
                 "w_gate": moe["experts"]["w_gate"],
                 "w_up": moe["experts"]["w_up"],
                 "w_down": moe["experts"]["w_down"],
                 "s_gate": se["gate_proj"]["kernel"],
                 "s_up": se["up_proj"]["kernel"],
                 "s_down": se["down_proj"]["kernel"]}
        if "mamba" in lp:
            mb = lp["mamba"]
            layer.update({
                "w_in": mb["in_proj"]["kernel"],
                "taps": mb["conv1d"]["kernel"],
                "conv_bias": mb["conv1d"]["bias"],
                "dt_bias": mb["dt_bias"], "A_log": mb["A_log"],
                "D": mb["D"], "gnorm": mb["norm"]["scale"],
                "w_out": mb["out_proj"]["kernel"]})
        else:
            att = lp["self_attn"]
            layer.update({
                "wq": att["q_proj"]["kernel"], "wk": att["k_proj"]["kernel"],
                "wv": att["v_proj"]["kernel"], "wo": att["o_proj"]["kernel"]})
        layers.append(layer)
    return {"embed": params["embed_tokens"]["embedding"], "layers": layers,
            "norm": params["norm"]["scale"]}


def shapes(hf: Dict[str, Any]) -> Dict[str, int]:
    """Shape facts for ``lib/costs.py``, ``lib/costs_paged.py``,
    ``lib/costs_moe.py`` and ``lib/costs_ssd.py`` (the module doc says how
    a family whose state is its cache fills them).  ``experts`` is what is
    HELD here, ``router_width`` the published count.  ``matmul_params``
    counts what one token multiplies by on this chip on average: the
    mixers' projections, the router, the shared expert,
    ``experts_per_token x held / router_width`` routed experts and the head
    (the tied embedding, once)."""
    h, v = hf["hidden_size"], hf["vocab_size"]
    hq, hkv = hf["num_attention_heads"], hf["num_key_value_heads"]
    d = h // hq
    hm, p, n, taps = hf["mamba_n_heads"], hf["mamba_d_head"], \
        hf["mamba_d_state"], hf["mamba_d_conv"]
    di = hm * p
    conv_dim = di + 2 * hf["mamba_n_groups"] * n
    e, er, k = hf["num_local_experts"], _router_width(hf), \
        hf["num_experts_per_tok"]
    f, fs = hf["intermediate_size"], hf["shared_intermediate_size"]
    layers = hf["num_hidden_layers"]
    attn_layers = sum(t == "attention" for t in hf["layer_types"])
    ssd_layers = layers - attn_layers
    attn = 2 * h * hq * d + 2 * h * hkv * d
    mamba = h * (di + conv_dim + hm) + di * h
    mamba_small = taps * conv_dim + conv_dim + 3 * hm + di
    moe_fixed = h * er + 3 * h * fs
    out = {"layers": layers, "hidden": h, "q_heads": hq, "kv_heads": hkv,
           "head_dim": d, "vocab": v,
           "attn_layers": attn_layers, "ssd_layers": ssd_layers,
           "ssd_heads": hm, "ssd_head_dim": p, "ssd_state": n,
           "conv_taps": taps, "conv_channels": conv_dim,
           "experts": e, "router_width": er, "experts_per_token": k,
           "expert_width": f,
           "matmul_params": attn_layers * attn + ssd_layers * mamba
           + layers * (moe_fixed + k * e * 3 * h * f // er) + h * v,
           "total_params": attn_layers * attn
           + ssd_layers * (mamba + mamba_small)
           + layers * (moe_fixed + e * 3 * h * f + 2 * h) + h * v + h,
           "kv_bytes_per_token": 2 * attn_layers * hkv * d * 2,
           "state_bytes_per_seq": ssd_layers * (n * di * 4
                                                + (taps - 1) * conv_dim * 2)}
    if "serve" in hf:
        out["state_slots"] = int(hf["serve"]["max_ragged_sequence_count"])
    return out
