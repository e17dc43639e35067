"""LongCat-Flash family adapter: from the published ``config.json`` keys
(``model_type: longcat_flash``, meituan-longcat/LongCat-Flash-Omni) to the
program's model object (``RaggedLongcatFlash``), to the plain reference's
parameter dict, and to the shape facts the FLOP/byte functions need.  The
only file that knows both namings.

**The share**, as ``families/moonlight.py``: ``n_routed_experts`` in the
configuration file is how many experts are HELD here (``reduced``),
``router_experts`` the published count, ``expert_start`` the first held id;
``zero_expert_num`` is not cut (the identity term is the token's own
chip's), so the router's width is ``router_experts + zero_expert_num``.

**Cache layers are attention sub-layers**, two a published layer: ``shapes``
gives ``layers`` = ``2 x num_layers`` (what ``lib/costs_mla.py`` multiplies a
read by and what the pool keeps a row for) and ``moe_layers`` =
``num_layers``; ``kv_row_bytes_per_token`` = sub-layers x 640 lanes x 2 B =
10,240 at four published layers.

**Seeded weights**, by ``families/moonlight.py``'s rules: embedding N(0, 1),
kernels N(0, 1/fan_in), the residual-writing kernels (``o_proj``, every
``down``) at 1/sqrt(2 L') of that with L' = the 8 sub-blocks of the four
published layers, norm weights 1, and three things of this model's own:

**``q_b_proj`` at ``Q_SCALE`` = 3 / s_q and ``kv_b_proj`` at ``KV_B_SCALE``
= 1 / s_kv of N(0, 1/fan_in).**  The two scale factors of the published
model (the query times 2, the normalised latent times 3.46) compensate for
what trained low-rank projections put out; on seeded kernels of unit gain
they multiply every attention score by 6.9.  With Moonlight's ``Q_SCALE`` of
3 on top the seeded scores had a standard deviation of ~17: every head a
one-hot over its context, and where the two largest scores of a row lie
within a bf16 rounding the served program and the float32 reference attend
to DIFFERENT tokens: the check read 0.66-0.75 with every layer right (v5e,
PR 52, call 1, four seeds), while the three latent kernels at 64 heads read
0.3-0.5% against plain compositions whatever the queries' size (call 2).
Dividing the two kernels by the factors gives the scores (std ~2.4) and the
values (unit) the spread they have in the Moonlight cell, whose reasons for
the 3 stand (a softmax over thousands of random keys must not be flat, or a
chunk that loses its cached context is not seen): 0.0063 / 0.0106 on two
seeds (call 2); ``Q_SCALE`` 0.433 with ``kv_b_proj`` at 1 (the same scores,
values 3.46 times larger) read 0.0153 / 0.0160.  The factors themselves are
applied by program and reference alike, and a program that leaves one out
is seen (PERF.md, PR 52, the fault table).

**The selection bias** ``e_score_correction_bias = BIAS_STD x z``, ``z`` the
seeded N(0, 1) leaf (``_SeededBias`` applies the mapping to the served
model's parameters on their way in, ``reference_params`` to the
reference's), kept in float32.  A softmax score over 768 outputs is 1.3e-3
on average and the chosen twelve score 7e-3 to 2e-2 with seeded weights (a
numpy draw of unit-normal logits): a bias of order 1 would choose the same
twelve outputs for every token, one of order 1e-5 none other than the
scores'.  By the same draw a bias of 1e-3 moves 16% of the slots, 2e-3 30%,
3e-3 48%, 5e-3 69%.  ISSUE 52 aimed at 20-40%; at 2e-3 the check does not
see a program that DROPS the bias (0.0286 against the limit of 0.03) nor
one that lets it INTO THE WEIGHTS (0.0224; v5e, PR 52, call 3); at 3e-3 it
reads 0.0423 / 0.0510 and at 4e-3 0.1025 / 0.1286 with the clean program at
0.0080 / 0.0077 (call 4).  3e-3 it is: the scores still decide every other
slot, and both faults are over the limit.

**The routed experts' down projections at ``EXPERT_DOWN`` of their residual
scale; the zero term as it is.**  A top-12 of 768 is a discontinuity that a
bf16 program and a float32 reference resolve differently on near ties.  With
16 of 512 experts held nearly every flip is between an expert held
elsewhere (adds nothing here) and either another such (invisible) or a zero
output (adds ``w m`` with ``w`` = 6 x 7e-3 = 0.04 for the marginal output:
a softmax router's marginal score is its smallest, unlike a sigmoid
router's) or, for one slot in fifty, an expert held here.  Measured with
every layer right: 0.0063-0.0106 over the sixteen readings of calls 2-3
(PERF.md, PR 52): a third of the limit, no further lever needed.
"""

from __future__ import annotations

from typing import Any, Dict

from benchmark.families.moonlight import _router_width

REFERENCE = "longcat_flash"

#: e_score_correction_bias = BIAS_STD * z (the module doc)
BIAS_STD = 3e-3
#: the routed experts' down projections, beside RESIDUAL_SCALE
EXPERT_DOWN = 0.25
#: s_q, s_kv at the published widths (sqrt(6144 / 1536), sqrt(6144 / 512))
_S_Q, _S_KV = 2.0, 12.0 ** 0.5
#: q_b_proj beside N(0, 1/fan_in): Moonlight's 3 over s_q (the module doc)
Q_SCALE = 3.0 / _S_Q
#: kv_b_proj beside N(0, 1/fan_in): 1 over s_kv (the module doc)
KV_B_SCALE = 1.0 / _S_KV


def program_config(hf: Dict[str, Any]):
    import jax.numpy as jnp

    from deepspeed_tpu.inference.v2.model_implementations. \
        ragged_longcat_flash import LongcatFlashConfig

    if hf.get("attention_bias") or hf.get("rope_scaling") is not None \
            or hf.get("attention_method", "MLA") != "MLA":
        raise ValueError("families/longcat_flash.py: attention_bias, "
                         "rope_scaling and an attention_method other than "
                         "MLA are not what LongCat-Flash publishes nor "
                         "what is implemented")
    return LongcatFlashConfig(
        vocab_size=hf["vocab_size"], hidden_size=hf["hidden_size"],
        ffn_hidden_size=hf["ffn_hidden_size"],
        expert_ffn_hidden_size=hf["expert_ffn_hidden_size"],
        num_layers=hf["num_layers"],
        num_attention_heads=hf["num_attention_heads"],
        kv_lora_rank=hf["kv_lora_rank"], q_lora_rank=hf["q_lora_rank"],
        qk_nope_head_dim=hf["qk_nope_head_dim"],
        qk_rope_head_dim=hf["qk_rope_head_dim"],
        v_head_dim=hf["v_head_dim"],
        mla_scale_q_lora=bool(hf["mla_scale_q_lora"]),
        mla_scale_kv_lora=bool(hf["mla_scale_kv_lora"]),
        n_routed_experts=_router_width(hf),
        held_experts=hf["n_routed_experts"],
        expert_start=int(hf.get("expert_start", 0)),
        zero_expert_num=hf["zero_expert_num"],
        zero_expert_type=hf["zero_expert_type"], moe_topk=hf["moe_topk"],
        routed_scaling_factor=float(hf["routed_scaling_factor"]),
        rope_theta=float(hf["rope_theta"]), rms_norm_eps=hf["rms_norm_eps"],
        latent_norm_eps=float(hf.get("latent_norm_eps", 1e-6)),
        max_position_embeddings=hf["max_position_embeddings"],
        dtype=jnp.bfloat16)


def _seeded_bias(tree, leaf: str):
    """The mapping of the module doc on every ``leaf`` of ``tree``."""
    def walk(node):
        if isinstance(node, list):
            return [walk(v) for v in node]
        if not isinstance(node, dict):
            return node
        out = {k: walk(v) for k, v in node.items()}
        if leaf in out:
            z = out[leaf]
            out[leaf] = (BIAS_STD * z.astype("float32")).astype("float32")
        return out

    return walk(tree)


class _SeededBias:
    """The served model with the seeded-bias mapping applied to the
    parameters on their way in (inside the step program: 768 values a
    layer, kept in float32: a bf16 bias of 3e-3 beside scores of 7e-3 would
    round the sum the selection sorts).  Everything else is the program's
    model."""

    def __init__(self, model):
        self._model = model

    def __getattr__(self, name):
        return getattr(self._model, name)

    def __call__(self, params, cache, batch, prefill_tile=None,
                 decode=False):
        return self._model(
            _seeded_bias(params, "e_score_correction_bias"), cache, batch,
            prefill_tile=prefill_tile, decode=decode)


def serve_model(hf: Dict[str, Any], block_size: int, mesh=None):
    from deepspeed_tpu.inference.v2.model_implementations. \
        ragged_longcat_flash import RaggedLongcatFlash

    return _SeededBias(RaggedLongcatFlash(program_config(hf), block_size,
                                          mesh=mesh))


def serve_param_shapes(hf: Dict[str, Any]):
    from deepspeed_tpu.inference.v2.model_implementations. \
        ragged_longcat_flash import param_shapes

    return param_shapes(program_config(hf))


#: what a residual-writing kernel is scaled by: the 1 / sqrt(2 L') of
#: scaled-residual initialisers at this configuration's L' = 8 sub-blocks
RESIDUAL_SCALE = 16 ** -0.5


def init_std(path_names, shape) -> Any:
    """Seeded-weight scale per leaf (the module doc)."""
    leaf, parent = path_names[-1], path_names[-2] if len(path_names) > 1 \
        else ""
    if leaf == "scale":
        return None
    if leaf in ("embedding", "e_score_correction_bias"):
        return 1.0                  # (the bias: z of the seeded mapping)
    if leaf == "w_down":
        return EXPERT_DOWN * RESIDUAL_SCALE * shape[1] ** -0.5
    if leaf in ("w_gate", "w_up"):
        return shape[1] ** -0.5
    if parent in ("o_proj", "down_proj"):
        return RESIDUAL_SCALE * shape[0] ** -0.5
    if parent == "q_b_proj":
        return Q_SCALE * shape[0] ** -0.5
    if parent == "kv_b_proj":
        return KV_B_SCALE * shape[0] ** -0.5
    return shape[0] ** -0.5


def reference_params(params) -> Dict[str, Any]:
    """Program tree -> the plain reference's dict (no copy, no cast beyond
    the seeded-bias mapping's few values)."""
    n = sum(1 for k in params if k.startswith("layers_"))
    layers = []
    for i in range(n):
        lp = params[f"layers_{i}"]
        subs = []
        for j in (0, 1):
            sp = lp[f"sub_{j}"]
            att, mlp = sp["self_attn"], sp["mlp"]
            subs.append({
                "ln1": sp["input_layernorm"]["scale"],
                "ln2": sp["post_attention_layernorm"]["scale"],
                "wqa": att["q_a_proj"]["kernel"],
                "q_norm": att["q_a_layernorm"]["scale"],
                "wqb": att["q_b_proj"]["kernel"],
                "wkva": att["kv_a_proj_with_mqa"]["kernel"],
                "kv_norm": att["kv_a_layernorm"]["scale"],
                "wkvb": att["kv_b_proj"]["kernel"],
                "wo": att["o_proj"]["kernel"],
                "gate": mlp["gate_proj"]["kernel"],
                "up": mlp["up_proj"]["kernel"],
                "down": mlp["down_proj"]["kernel"]})
        moe = lp["mlp"]
        layers.append({
            "subs": subs, "router": moe["gate"]["wg"]["kernel"],
            "bias": moe["gate"]["e_score_correction_bias"],
            "w_gate": moe["experts"]["w_gate"],
            "w_up": moe["experts"]["w_up"],
            "w_down": moe["experts"]["w_down"]})
    return _seeded_bias(
        {"embed": params["embed_tokens"]["embedding"], "layers": layers,
         "norm": params["norm"]["scale"],
         "lm_head": params["lm_head"]["kernel"]}, "bias")


def param_counts(hf: Dict[str, Any]) -> Dict[str, int]:
    """Parameters by part, from the published keys alone: one attention
    sub-layer (its two latent norms included), one dense FFN, the router
    (weights and biases), the four layer norms, one expert, embedding +
    head, the final norm; ``layer`` one published layer with the experts
    HELD here, ``total`` the whole configuration."""
    h, v = hf["hidden_size"], hf["vocab_size"]
    hq, qr = hf["num_attention_heads"], hf["q_lora_rank"]
    rank, nope, rope, vd = hf["kv_lora_rank"], hf["qk_nope_head_dim"], \
        hf["qk_rope_head_dim"], hf["v_head_dim"]
    width = _router_width(hf) + hf["zero_expert_num"]
    mla = h * qr + qr + qr * hq * (nope + rope) + h * (rank + rope) + rank \
        + rank * hq * (nope + vd) + hq * vd * h
    ffn = 3 * h * hf["ffn_hidden_size"]
    expert = 3 * h * hf["expert_ffn_hidden_size"]
    router = h * width + width
    layer = 2 * (mla + ffn) + router + 4 * h \
        + hf["n_routed_experts"] * expert
    return {"mla": mla, "ffn": ffn, "router": router, "norms": 4 * h,
            "expert": expert, "layer": layer,
            "total": hf["num_layers"] * layer + 2 * h * v + h}


def shapes(hf: Dict[str, Any]) -> Dict[str, int]:
    """Shape facts for ``lib/costs.py``, ``lib/costs_moe.py`` and
    ``lib/costs_mla.py`` (the module doc: ``layers`` counts attention
    sub-layers).  ``experts`` is what is HELD here, ``router_width`` the
    router's outputs, zero-compute ones included.  ``matmul_params`` counts
    what one token multiplies by on this chip on average: per published
    layer two attentions' projections, two dense FFNs, the router and
    ``moe_topk x held / router_width`` routed experts; the lm_head."""
    h, v = hf["hidden_size"], hf["vocab_size"]
    rank, rope = hf["kv_lora_rank"], hf["qk_rope_head_dim"]
    e, k = hf["n_routed_experts"], hf["moe_topk"]
    width = _router_width(hf) + hf["zero_expert_num"]
    n = param_counts(hf)
    layers = hf["num_layers"]
    mla_mm = n["mla"] - hf["q_lora_rank"] - rank       # without its norms
    row = -(-(rank + rope) // 128) * 128
    return {"layers": 2 * layers, "hidden": h,
            "q_heads": hf["num_attention_heads"], "kv_heads": 1,
            "head_dim": rank + rope, "vocab": v,
            "q_lora_rank": hf["q_lora_rank"], "kv_lora_rank": rank,
            "qk_nope_head_dim": hf["qk_nope_head_dim"],
            "qk_rope_head_dim": rope, "v_head_dim": hf["v_head_dim"],
            "dense_layers": 0, "moe_layers": layers,
            "experts": e, "router_width": width,
            "zero_experts": hf["zero_expert_num"],
            "experts_per_token": k,
            "expert_width": hf["expert_ffn_hidden_size"],
            "matmul_params": layers * (
                2 * (mla_mm + n["ffn"]) + h * width
                + k * e * n["expert"] // width) + h * v,
            "total_params": n["total"],
            "kv_bytes_per_token": 2 * layers * (rank + rope) * 2,
            "kv_row_bytes_per_token": 2 * layers * row * 2}
