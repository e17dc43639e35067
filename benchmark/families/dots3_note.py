"""dots3-note family adapter: from the published ``config.json`` keys
(``model_type: dots3_note``, dots-studio/dots3-note-prev) to the program's
model object (``RaggedDots3Note``), to the plain reference's parameter dict,
and to the shape facts the FLOP/byte functions need.  The only file that
knows both namings.

**The share**, as ``families/moonlight.py``: ``n_routed_experts`` in the
configuration file is how many experts are HELD here (``reduced``),
``router_experts`` the published count, ``expert_start`` the first held id.

**Two kinds of layer, two geometries.**  ``shapes`` states the FULL layers'
geometry under the names ``lib/costs_dsa.py`` reads (``q_heads``,
``kv_lora_rank``, ``index_*``), and ``layers`` is the number of FULL layers,
because that function multiplies a layer's indexer and sparse read by it
(the program's ``idx_*`` / ``sel_*`` counters are a layer's); the sliding
layers' geometry stands beside it under ``swa_*`` with ``window_layers`` and
``window`` (``lib/costs_window_latent.py``), ``all_layers`` is the depth,
``moe_layers`` / ``dense_layers`` count FFNs over the whole depth; ``win_pool_blocks`` is the
window group's pool as the state manager sizes it from the ``serve`` group
(``ragged_manager.py::window_pool_blocks``: what ``win_live_pct`` is a share
of).
``kv_bytes_per_token`` is what the GLOBAL pool keeps a token in content
(full layers x (512 + 64 + 128) x 2 B: the pool the runner's log line and
``kv_live_pct`` are about), ``kv_row_bytes_per_token`` the same with the
latent row's lane padding (full layers x (640 + 128) x 2 B = 3,072 at two
full layers: the program's ``per_token_bytes``), ``win_row_bytes_per_token``
the window pool's (sliding layers x 1,152 x 2 B = 6,912 at three).

**Seeded weights**, by ``families/moonlight.py``'s rules (embedding N(0, 1),
kernels N(0, 1/fan_in), norm weights 1, ``k_norm``'s bias 0, residual-writing
kernels at 1/sqrt(2 L) of that with L = 5, the routed experts' down
projections at ``EXPERT_DOWN`` of that again) and, as
``families/longcat_flash.py`` found for a model with scaled latents:
``q_b_proj`` at 3 / s_q and ``kv_b_proj`` at 1 / s_kv of N(0, 1/fan_in),
each from its OWN fan-in (``s = sqrt(hidden / fan_in)``: 2.24 and 2.24 on a
sliding layer, 2.24 and 3.16 on a full one), so that the seeded scores and
values have the spread they have in the Moonlight cell and not 5 or 7 times
it (every head a one-hot over its context, where a bf16 program and a
float32 reference attend to different tokens).  The factors themselves are
applied by program and reference alike, and a program that drops or swaps
them is seen (PERF.md, PR 61, the fault table).  ``gate_proj`` (the head
gate) is N(0, 1/fan_in): gate logits of unit spread, gates around 0.5.

**``o_proj`` at ``ATTN_OUT`` = 0.7 of its residual scale**, GLM-5's question
(``families/glm_moe_dsa.py``) asked again with this model's own readings.
The full layers' top-2,048 is a discontinuity a bf16 program and the
float32 reference resolve differently at positions whose index score lies
within a rounding of the threshold, and GLM-5's clean reading grew with
``o_proj``'s scale (0.014 at 0.3, 0.021 at 0.5, 0.051 at 1).  Here it does
NOT, up to 0.7: the check read 0.0076 / 0.0086 / 0.0069 / 0.0082 at 0.3,
0.0076 / 0.0081 / 0.0069 at 0.5 and 0.0077 / 0.0071 at 0.7 (v5e, PR 61,
calls 1-2, a seed each): the gate halves a head's output and two layers in
five select, so what a swapped position moves stays under the other
roundings' 0.007.  The faults do grow with it: the indexer skipped read
0.026 at 0.3 (NOT seen), 0.046 at 0.5, 0.064 at 0.7; the band's first block
released early 0.028 at 0.3; the gate laid along a head's values 0.029 at
0.3, 0.043 at 0.5.  0.7 keeps every clean reading under a third of the
limit and the skipped indexer at twice it; 1 was not tried (GLM-5's 0.051
there is why).  The sliding layers have no such discontinuity (a window is
the same set in both programs) and take the same factor: one rule a leaf
name.  What the check still cannot see at any scale: the window one key
wide or narrow (one key of 513 in three layers) and the two rescales
swapped on a full layer (the scores' product is the same; the values change
by ``s_q / s_kv`` = 0.71 behind a softmax the swap also flattens):
``tests/unit/test_ragged_dots3_note.py`` sees both at float32.
"""

from __future__ import annotations

from typing import Any, Dict

from benchmark.families import moonlight as base
from benchmark.families.moonlight import EXPERT_DOWN

REFERENCE = "dots3_note"

#: handed to the program's config under their published names
_PUBLISHED_KEYS = ("swa_num_attention_heads", "swa_kv_lora_rank",
             "swa_q_lora_rank", "swa_qk_nope_head_dim",
             "swa_qk_rope_head_dim", "swa_v_head_dim", "swa_rope_theta",
             "swa_attention_gate_type", "sliding_window_size",
             "attention_gate_type", "apply_mla_qkv_lora_rescale",
             "index_n_heads", "index_head_dim", "index_topk")


def program_config(hf: Dict[str, Any]):
    import jax.numpy as jnp

    from deepspeed_tpu.inference.v2.model_implementations. \
        ragged_dots3_note import Dots3NoteConfig

    if hf.get("attention_bias") or hf.get("rope_scaling") is not None:
        raise ValueError("families/dots3_note.py: attention_bias and "
                         "rope_scaling are not what dots3-note-prev "
                         "publishes nor what is implemented")
    return Dots3NoteConfig(
        vocab_size=hf["vocab_size"], hidden_size=hf["hidden_size"],
        intermediate_size=hf["intermediate_size"],
        moe_intermediate_size=hf["moe_intermediate_size"],
        num_hidden_layers=hf["num_hidden_layers"],
        layer_types=list(hf["layer_types"]),
        num_attention_heads=hf["num_attention_heads"],
        kv_lora_rank=hf["kv_lora_rank"], q_lora_rank=hf["q_lora_rank"],
        qk_nope_head_dim=hf["qk_nope_head_dim"],
        qk_rope_head_dim=hf["qk_rope_head_dim"],
        v_head_dim=hf["v_head_dim"], rope_theta=float(hf["rope_theta"]),
        index_norm_eps=float(hf.get("index_norm_eps", 1e-6)),
        **{k: hf[k] for k in _PUBLISHED_KEYS},
        n_routed_experts=base._router_width(hf),
        held_experts=hf["n_routed_experts"],
        expert_start=int(hf.get("expert_start", 0)),
        n_shared_experts=hf["n_shared_experts"],
        num_experts_per_tok=hf["num_experts_per_tok"],
        first_k_dense_replace=hf["first_k_dense_replace"],
        moe_layer_freq=hf["moe_layer_freq"],
        n_group=int(hf.get("n_group", 1)),
        topk_group=int(hf.get("topk_group", 1)),
        norm_topk_prob=bool(hf["norm_topk_prob"]),
        routed_scaling_factor=float(hf["routed_scaling_factor"]),
        scoring_func=hf["scoring_func"], topk_method=hf["topk_method"],
        rms_norm_eps=hf["rms_norm_eps"],
        latent_norm_eps=float(hf.get("latent_norm_eps", 1e-6)),
        max_position_embeddings=hf["max_position_embeddings"],
        dtype=jnp.bfloat16)


def serve_model(hf: Dict[str, Any], block_size: int, mesh=None):
    from deepspeed_tpu.inference.v2.model_implementations. \
        ragged_dots3_note import RaggedDots3Note

    return base._SeededBias(RaggedDots3Note(program_config(hf), block_size,
                                            mesh=mesh))


def serve_param_shapes(hf: Dict[str, Any]):
    from deepspeed_tpu.inference.v2.model_implementations. \
        ragged_dots3_note import param_shapes

    return param_shapes(program_config(hf))


#: what a residual-writing kernel is scaled by: the 1 / sqrt(2 L) of
#: scaled-residual initialisers at this configuration's L = 5
RESIDUAL_SCALE = 10 ** -0.5
#: o_proj beside RESIDUAL_SCALE x N(0, 1/fan_in): the module doc
ATTN_OUT = 0.7
#: q_b_proj beside N(0, 1/fan_in) / s_q: how sharp the seeded softmax is
Q_SCALE = 3.0
#: the published hidden size: s_q, s_kv = sqrt(HIDDEN / a projection's fan-in)
HIDDEN = 5120


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
    if parent == "q_b_proj":        # 3 / s_q, s_q = sqrt(HIDDEN / fan_in)
        return Q_SCALE * HIDDEN ** -0.5
    if parent == "kv_b_proj":       # 1 / s_kv
        return HIDDEN ** -0.5
    return shape[0] ** -0.5


def reference_params(params) -> Dict[str, Any]:
    """Program tree -> the plain reference's dict: Moonlight's mapping (the
    FFN blocks, the seeded-bias mapping; ``q_b_proj`` handed to it where it
    looks for ``q_proj``) with the low-rank query's, the gate's and, on a
    full layer, the indexer's leaves."""
    ref = base.reference_params({
        k: {**v, "self_attn": {**v["self_attn"],
                               "q_proj": v["self_attn"]["q_b_proj"]}}
        if k.startswith("layers_") else v for k, v in params.items()})
    for i, layer in enumerate(ref["layers"]):
        att = params[f"layers_{i}"]["self_attn"]
        layer["wqb"] = layer.pop("wq")
        layer.update({"wqa": att["q_a_proj"]["kernel"],
                      "q_norm": att["q_a_layernorm"]["scale"],
                      "wg": att["gate_proj"]["kernel"]})
        if "indexer" in att:
            ix = att["indexer"]
            layer.update({
                "wiq": ix["wq_b"]["kernel"], "wik": ix["wk"]["kernel"],
                "ik_norm_w": ix["k_norm"]["scale"],
                "ik_norm_b": ix["k_norm"]["bias"],
                "wiw": ix["weights_proj"]["kernel"]})
    return ref


def shapes(hf: Dict[str, Any]) -> Dict[str, int]:
    """Shape facts for ``lib/costs_dsa.py`` and
    ``lib/costs_window_latent.py`` (the module doc: ``layers`` counts the
    FULL layers).  ``matmul_params`` counts what one token multiplies by on
    this chip on average: each layer's attention at its own kind's widths,
    per MoE layer the router, the shared expert and ``experts_per_token x
    held / router_width`` routed experts, the dense layer, the lm_head."""
    h, v = hf["hidden_size"], hf["vocab_size"]
    kinds = list(hf["layer_types"])
    n_full = kinds.count("full_attention")
    n_win = kinds.count("sliding_attention")
    hi, di = hf["index_n_heads"], hf["index_head_dim"]

    def attention(pre):
        hq, qr, rank = hf[pre + "num_attention_heads"], \
            hf[pre + "q_lora_rank"], hf[pre + "kv_lora_rank"]
        nope, rope, vd = hf[pre + "qk_nope_head_dim"], \
            hf[pre + "qk_rope_head_dim"], hf[pre + "v_head_dim"]
        return (h * qr + qr * hq * (nope + rope) + h * (rank + rope)
                + rank * hq * (nope + vd) + hq * vd * h + h * hq,
                qr + rank)

    full, full_norms = attention("")
    swa, swa_norms = attention("swa_")
    full += hf["q_lora_rank"] * hi * di + h * di + h * hi
    e, er, k = hf["n_routed_experts"], base._router_width(hf), \
        hf["num_experts_per_tok"]
    f, fd = hf["moe_intermediate_size"], hf["intermediate_size"]
    fs = hf["n_shared_experts"] * f
    layers = n_full + n_win
    dense = min(int(hf["first_k_dense_replace"]), layers)
    moe_layers = layers - dense
    moe_fixed = h * er + 3 * h * fs
    row = lambda rank, rope: -(-(rank + rope) // 128) * 128
    rank, rope = hf["kv_lora_rank"], hf["qk_rope_head_dim"]
    out = {"layers": n_full, "all_layers": layers, "hidden": h, "vocab": v,
            "q_heads": hf["num_attention_heads"], "kv_heads": 1,
            "head_dim": rank + rope,
            "q_lora_rank": hf["q_lora_rank"], "kv_lora_rank": rank,
            "qk_nope_head_dim": hf["qk_nope_head_dim"],
            "qk_rope_head_dim": rope, "v_head_dim": hf["v_head_dim"],
            "index_heads": hi, "index_head_dim": di,
            "index_topk": hf["index_topk"],
            "window_latent_layers": n_win,
            "window": hf["sliding_window_size"],
            "swa_q_heads": hf["swa_num_attention_heads"],
            "swa_kv_lora_rank": hf["swa_kv_lora_rank"],
            "swa_qk_nope_head_dim": hf["swa_qk_nope_head_dim"],
            "swa_qk_rope_head_dim": hf["swa_qk_rope_head_dim"],
            "swa_v_head_dim": hf["swa_v_head_dim"],
            "dense_layers": dense, "moe_layers": moe_layers,
            "experts": e, "router_width": er, "experts_per_token": k,
            "expert_width": f,
            "matmul_params": n_full * full + n_win * swa
            + dense * 3 * h * fd
            + moe_layers * (moe_fixed + k * e * 3 * h * f // er) + h * v,
            "total_params": n_full * (full + full_norms + 2 * di)
            + n_win * (swa + swa_norms) + layers * 2 * h
            + dense * 3 * h * fd
            + moe_layers * (moe_fixed + er + e * 3 * h * f) + 2 * h * v + h,
            "kv_bytes_per_token": n_full * (rank + rope + di) * 2,
            "kv_row_bytes_per_token": n_full * (row(rank, rope) + di) * 2,
            "win_row_bytes_per_token": n_win * row(
                hf["swa_kv_lora_rank"], hf["swa_qk_rope_head_dim"]) * 2}
    serve = hf.get("serve")
    if serve:       # the window pool as the state manager sizes it
        seqs, bs = int(serve["max_ragged_sequence_count"]), \
            int(serve["block_size"])
        q, r = divmod(out["window"] - 1, bs)
        out["win_pool_blocks"] = seqs * (q + 2) + (
            seqs * r + max(int(serve["token_budget"]), seqs)) // bs
    return out
