"""GLM-5 family adapter: from the published ``config.json`` keys
(``model_type: glm_moe_dsa``, zai-org/GLM-5) to the program's model object
(``RaggedDeepseekV3`` with a low-rank query and an indexer), to the plain
reference's parameter dict, and to the shape facts the FLOP/byte functions
need.  The only file that knows both namings.

What it shares with the Moonlight adapter (``families/moonlight.py``, whose
doc says why each is so) it takes from there: **the share** of the experts
(``n_routed_experts`` HELD here, ``router_experts`` the router's width,
``expert_start``), the seeded selection bias and its mapping
(``_SeededBias``), and the seeded weights' rules: embedding N(0, 1), kernels
N(0, 1/fan_in), residual-writing kernels at 1/sqrt(2 L) of that (L = 5
here), the routed experts' down projections at ``EXPERT_DOWN`` of that
again, the last query projection (``q_b_proj`` here) at ``Q_SCALE``, and
``o_proj`` at ``ATTN_OUT`` of its residual scale (below).  The
new leaves (``q_a_proj``, the indexer's ``wq_b``, ``wk``, ``weights_proj``)
are N(0, 1/fan_in), norm weights 1, ``k_norm``'s bias 0.

**``o_proj`` at ``ATTN_OUT`` of its residual scale.**  A top-k is a
discontinuity, as the router is: a cached position whose index score lies
within a bf16 rounding of the 2,048th largest is chosen otherwise by the
served program than by the float32 reference (the two differ by a fraction
of a percent in every activation that feeds the indexer; of the check's
6,144 positions some tens lie that close to the threshold a row), and a
seeded indexer's scores are independent of the attention's own, so a
swapped position may be one a seeded head attends to (a trained indexer's
marginal positions are the attention's marginal ones).  With every layer
right the check read 0.0506 at ``ATTN_OUT`` 1 (v5e, PR 50, call 1), over the
accepted limit of 0.03; 0.0218 / 0.0210 / 0.0209 at 0.5 (call 2) and, at
0.3 over twelve seeds (calls 2-3), 0.0090 .. 0.0191, median 0.0137; over
the 34 seeds of all of PR 50's calls the same and ONE of 0.0248 (call 7),
which read 0.0133 at 0.1 and 0.0548 at 1 on its seed (call 8): swapped
positions, not a routing flip.  The faults read, at 0.5 and 0.3: the indexer dropped (every
position read) 0.111 / 0.060-0.066, the most recent 2,048 in place of the
best 0.176 / 0.079-0.108, the indexer's rotary missing 0.112 / 0.066-0.071,
the reference below bf16 0.067 / 0.056-0.057.  Not reliably seen at any
scale: one block short of 2,048 (0.032 / 0.0175-0.0255: six percent of the
set is the size of the rounding swaps themselves) and the index row at
float8's 3 mantissa bits (0.0174 beside a clean 0.0137: twice the swaps).
0.3 keeps all but one of 34 clean readings under two thirds of the limit
(that one at 0.83 of it) and the three faults at twice the limit or more;
what the check cannot judge at this scale, and the comparison that would
let the scale go back to 1: PERF.md section 7, PR 50 (4b).

**Two pool rows.**  ``kv_bytes_per_token`` is what the mathematics needs a
token to keep in BOTH leaves (layers x (512 + 64 + 128) x 2 B), and
``kv_row_bytes_per_token`` what the pool holds with the latent row's lane
padding (layers x (640 + 128) x 2 B = 7,680 at five layers: the program's
``BlockedKVCache.per_token_bytes``).
"""

from __future__ import annotations

from typing import Any, Dict

from benchmark.families import moonlight as base
from benchmark.families.moonlight import EXPERT_DOWN, Q_SCALE
from benchmark.reference.glm_moe_dsa import rope_theta

REFERENCE = "glm_moe_dsa"


def program_config(hf: Dict[str, Any]):
    import dataclasses

    rope = hf.get("rope_parameters") or {}
    if rope.get("rope_type", "default") != "default":
        raise ValueError(f"rope_type {rope.get('rope_type')!r}: plain rope "
                         f"is what GLM-5 publishes and what is implemented")
    cfg = base.program_config({**hf, "rope_theta": rope_theta(hf)})
    return dataclasses.replace(
        cfg, index_n_heads=int(hf["index_n_heads"]),
        index_head_dim=int(hf["index_head_dim"]),
        index_topk=int(hf["index_topk"]),
        index_norm_eps=float(hf.get("index_norm_eps", 1e-6)))


def serve_model(hf: Dict[str, Any], block_size: int, mesh=None):
    from deepspeed_tpu.inference.v2.model_implementations. \
        ragged_deepseek_v3 import RaggedDeepseekV3

    return base._SeededBias(RaggedDeepseekV3(program_config(hf), block_size,
                                             mesh=mesh))


def serve_param_shapes(hf: Dict[str, Any]):
    from deepspeed_tpu.inference.v2.model_implementations. \
        ragged_deepseek_v3 import param_shapes

    return param_shapes(program_config(hf))


#: what a residual-writing kernel is scaled by: the 1 / sqrt(2 L) of
#: scaled-residual initialisers at this configuration's L = 5
RESIDUAL_SCALE = 10 ** -0.5
#: o_proj beside RESIDUAL_SCALE x N(0, 1/fan_in): the module doc
ATTN_OUT = 0.3


def init_std(path_names, shape) -> Any:
    """Seeded-weight scale per leaf (the module doc)."""
    leaf, parent = path_names[-1], path_names[-2] if len(path_names) > 1 \
        else ""
    if leaf == "scale":
        return None
    if leaf == "bias":              # k_norm's: 0
        return 0.0
    if leaf in ("embedding", "e_score_correction_bias"):
        return 1.0
    if leaf == "w_down":
        return EXPERT_DOWN * RESIDUAL_SCALE * shape[1] ** -0.5
    if leaf in ("w_gate", "w_up"):
        return shape[1] ** -0.5
    if parent == "o_proj":
        return ATTN_OUT * RESIDUAL_SCALE * shape[0] ** -0.5
    if parent == "down_proj":
        return RESIDUAL_SCALE * shape[0] ** -0.5
    if parent == "q_b_proj":
        return Q_SCALE * shape[0] ** -0.5
    return shape[0] ** -0.5


def reference_params(params) -> Dict[str, Any]:
    """Program tree -> the plain reference's dict: Moonlight's mapping (the
    FFN blocks, the seeded-bias mapping; ``q_b_proj`` handed to it where it
    looks for ``q_proj``) with the low-rank query's and the indexer's
    leaves."""
    ref = base.reference_params({
        k: {**v, "self_attn": {**v["self_attn"],
                               "q_proj": v["self_attn"]["q_b_proj"]}}
        if k.startswith("layers_") else v for k, v in params.items()})
    for i, layer in enumerate(ref["layers"]):
        att = params[f"layers_{i}"]["self_attn"]
        ix = att["indexer"]
        layer["wqb"] = layer.pop("wq")
        layer.update({
            "wqa": att["q_a_proj"]["kernel"],
            "q_norm": att["q_a_layernorm"]["scale"],
            "wiq": ix["wq_b"]["kernel"], "wik": ix["wk"]["kernel"],
            "ik_norm_w": ix["k_norm"]["scale"],
            "ik_norm_b": ix["k_norm"]["bias"],
            "wiw": ix["weights_proj"]["kernel"]})
    return ref


def shapes(hf: Dict[str, Any]) -> Dict[str, int]:
    """Shape facts for ``lib/costs.py``, ``lib/costs_moe.py`` and
    ``lib/costs_dsa.py``: Moonlight's (``experts`` HELD here,
    ``router_width`` the published count; ``matmul_params`` what one token
    multiplies by on this chip on average) with the low-rank query and the
    indexer in the attention's count and both pool rows in the bytes a
    token keeps (the module doc)."""
    h, v = hf["hidden_size"], hf["vocab_size"]
    hq, qr = hf["num_attention_heads"], hf["q_lora_rank"]
    rank, nope, rope, vd = hf["kv_lora_rank"], hf["qk_nope_head_dim"], \
        hf["qk_rope_head_dim"], hf["v_head_dim"]
    hi, di = hf["index_n_heads"], hf["index_head_dim"]
    e, er, k = hf["n_routed_experts"], base._router_width(hf), \
        hf["num_experts_per_tok"]
    f, fd = hf["moe_intermediate_size"], hf["intermediate_size"]
    fs = hf["n_shared_experts"] * f
    layers = hf["num_hidden_layers"]
    dense = min(int(hf["first_k_dense_replace"]), layers)
    moe_layers = layers - dense
    indexer = qr * hi * di + h * di + h * hi
    attn = h * qr + qr * hq * (nope + rope) + h * (rank + rope) \
        + rank * hq * (nope + vd) + hq * vd * h + indexer
    # norms: two a layer, the two latent norms, k_norm's weight and bias
    norms = 2 * h + qr + rank + 2 * di
    moe_fixed = h * er + 3 * h * fs
    row = -(-(rank + rope) // 128) * 128
    return {"layers": layers, "hidden": h, "q_heads": hq, "kv_heads": 1,
            "head_dim": rank + rope, "vocab": v,
            "q_lora_rank": qr, "kv_lora_rank": rank,
            "qk_nope_head_dim": nope, "qk_rope_head_dim": rope,
            "v_head_dim": vd, "index_heads": hi, "index_head_dim": di,
            "index_topk": hf["index_topk"],
            "dense_layers": dense, "moe_layers": moe_layers,
            "experts": e, "router_width": er, "experts_per_token": k,
            "expert_width": f,
            "matmul_params": layers * attn + dense * 3 * h * fd
            + moe_layers * (moe_fixed + k * e * 3 * h * f // er) + h * v,
            "total_params": layers * (attn + norms) + dense * 3 * h * fd
            + moe_layers * (moe_fixed + er + e * 3 * h * f) + 2 * h * v + h,
            "kv_bytes_per_token": layers * (rank + rope + di) * 2,
            "kv_row_bytes_per_token": layers * (row + di) * 2}
