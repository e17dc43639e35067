"""Moonlight family adapter: from the published ``config.json`` keys
(``model_type: deepseek_v3``, moonshotai/Moonlight-16B-A3B) to the program's
model object (``RaggedDeepseekV3``), to the plain reference's parameter
dict, and to the shape facts the FLOP/byte functions need.  The only file
that knows both namings.

**The share.**  ``n_routed_experts`` in the configuration file is how many
experts are HELD here (``reduced``); ``router_experts`` beside it is the
published count, the router's width; ``expert_start`` the first held id.

**A latent row.**  A family whose cache keeps a latent row and not per-head
keys and values fills ``shapes`` so: ``kv_heads`` 1 and ``head_dim`` the
row's CONTENT (``kv_lora_rank + qk_rope_head_dim``), ``kv_bytes_per_token``
what the mathematics needs a token to keep (layers x content x 2 B:
14,976), and ``kv_row_bytes_per_token`` what the pool holds with its lane
padding (layers x 640 x 2 B: 16,640; the program's
``BlockedKVCache.per_token_bytes``).

**Seeded weights.**  Embedding N(0, 1), kernels N(0, 1/fan_in), the
residual-writing kernels (``o_proj``, every ``down``) at 1/sqrt(2 L) of
that (the Qwen3-Next family's scaled-residual scales, for its reason: a
network whose layers rewrite the stream carries a bf16 rounding to several
percent of the logit scale whatever the engine does), norm weights 1.

**The routed experts' down projections at ``EXPERT_DOWN`` of that again.**
This router gives each of a token's six experts about a sixth of 2.446 =
0.41 of weight, the marginal one as much as the best (a softmax router
gives its marginal expert little), so ONE near-tie that a bf16 rounding of
the router's input decides the other way swaps 0.41 of an expert's output
for another's.  That happens in a few percent of (token, layer) pairs: most
checks of 9 rows x 12 routed layers hold one, and at scale 1 it moves its
row's logits by 0.03-0.08 of the largest (measured: PERF.md, PR 31; the
rows without one read 0.008-0.011), over the accepted limit of 0.03 with
every layer right.  A trained network's experts are not independent random
maps.  At 1/4 a flip reads 0.01-0.02.

**``q_proj`` at ``Q_SCALE`` x N(0, 1/fan_in).**  At scale 1 a seeded head's
scores have unit spread: the softmax over a context of thousands of random
keys is nearly flat, its output the mean of hundreds of random values, a
twenty-fifth of a value's size, and a prompt chunk that LOSES the cached
context of the chunks before it moves the logits by 0.028 / 0.032 against a
limit of 0.03 (v5e, PR 31, call 3): not reliably seen.  At 3 a head attends
to a handful of keys and the fault reads 0.227 (call 4; 0.087-0.091 at 2),
while the clean program reads what it read before (0.013-0.014).

**The selection bias** ``e_score_correction_bias = BIAS_MEAN + BIAS_STD x
z``, ``z`` the seeded N(0, 1) leaf (``_SeededBias`` applies the mapping to
the served model's parameters on their way in, ``reference_params`` to the
reference's; the program's model is untouched).  The published buffer is
trained from zero.  The spread (0.1 against the scores' 0.21) makes a
program that DROPS the bias choose other experts for most tokens (0.048 /
0.050, call 3).  The common offset changes no selection (top-k is
shift-invariant) and nothing in a correct program (the clean check reads
the same at -0.5, -0.7, -0.8 and -0.9); it is there for a program that lets
the bias INTO THE WEIGHTS: the chosen scores are 0.75-0.97, so at -0.9 some
of ``s + b`` cross zero and ``(s + b) / sum(s + b)`` is far from ``s /
sum(s)``: 0.84 / 1.08 (call 4).  At -0.5 that fault read 0.016-0.017 and at
-0.7 0.020, under the limit; at -0.8 0.072 / 0.37.
"""

from __future__ import annotations

from typing import Any, Dict

REFERENCE = "moonlight"

#: e_score_correction_bias = BIAS_MEAN + BIAS_STD * z (the module doc)
BIAS_MEAN, BIAS_STD = -0.9, 0.1
#: the routed experts' down projections, beside RESIDUAL_SCALE
EXPERT_DOWN = 0.25
#: q_proj beside N(0, 1/fan_in): how sharp the seeded softmax is
Q_SCALE = 3.0


def program_config(hf: Dict[str, Any]):
    import jax.numpy as jnp

    from deepspeed_tpu.inference.v2.model_implementations. \
        ragged_deepseek_v3 import DeepseekV3Config

    # (attention_bias, rope_scaling, num_nextn_predict_layers: unset or 0 in
    # the published config; the reference refuses a configuration that sets
    # one)
    return DeepseekV3Config(
        vocab_size=hf["vocab_size"], hidden_size=hf["hidden_size"],
        intermediate_size=hf["intermediate_size"],
        moe_intermediate_size=hf["moe_intermediate_size"],
        num_hidden_layers=hf["num_hidden_layers"],
        num_attention_heads=hf["num_attention_heads"],
        kv_lora_rank=hf["kv_lora_rank"], q_lora_rank=hf["q_lora_rank"],
        qk_nope_head_dim=hf["qk_nope_head_dim"],
        qk_rope_head_dim=hf["qk_rope_head_dim"],
        v_head_dim=hf["v_head_dim"],
        n_routed_experts=_router_width(hf),
        held_experts=hf["n_routed_experts"],
        expert_start=int(hf.get("expert_start", 0)),
        n_shared_experts=hf["n_shared_experts"],
        num_experts_per_tok=hf["num_experts_per_tok"],
        first_k_dense_replace=hf["first_k_dense_replace"],
        moe_layer_freq=hf["moe_layer_freq"],
        n_group=hf["n_group"], topk_group=hf["topk_group"],
        norm_topk_prob=bool(hf["norm_topk_prob"]),
        routed_scaling_factor=float(hf["routed_scaling_factor"]),
        scoring_func=hf["scoring_func"], topk_method=hf["topk_method"],
        rope_theta=float(hf["rope_theta"]), rms_norm_eps=hf["rms_norm_eps"],
        latent_norm_eps=float(hf.get("latent_norm_eps", 1e-6)),
        max_position_embeddings=hf["max_position_embeddings"],
        dtype=jnp.bfloat16)


def _router_width(hf: Dict[str, Any]) -> int:
    return int(hf.get("router_experts", hf["n_routed_experts"]))


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
            out[leaf] = (BIAS_MEAN + BIAS_STD * z.astype("float32")
                         ).astype(z.dtype)
        return out

    return walk(tree)


class _SeededBias:
    """The served model with the seeded-bias mapping applied to the
    parameters on their way in (inside the step program: 64 values a
    layer).  Everything else is the program's model."""

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
        ragged_deepseek_v3 import RaggedDeepseekV3

    return _SeededBias(RaggedDeepseekV3(program_config(hf), block_size,
                                        mesh=mesh))


def serve_param_shapes(hf: Dict[str, Any]):
    from deepspeed_tpu.inference.v2.model_implementations. \
        ragged_deepseek_v3 import param_shapes

    return param_shapes(program_config(hf))


#: what a residual-writing kernel is scaled by: the 1 / sqrt(2 L) of
#: scaled-residual initialisers at this configuration's L = 13
RESIDUAL_SCALE = 26 ** -0.5


def init_std(path_names, shape) -> Any:
    """Seeded-weight scale per leaf (the module doc)."""
    leaf, parent = path_names[-1], path_names[-2] if len(path_names) > 1 \
        else ""
    if leaf == "scale":
        return None
    if leaf == "embedding":
        return 1.0
    if leaf == "e_score_correction_bias":
        return 1.0                  # z of the seeded-bias mapping
    if leaf == "w_down":
        return EXPERT_DOWN * RESIDUAL_SCALE * shape[1] ** -0.5
    if leaf in ("w_gate", "w_up"):
        return shape[1] ** -0.5
    if parent in ("o_proj", "down_proj"):
        return RESIDUAL_SCALE * shape[0] ** -0.5
    if parent == "q_proj":
        return Q_SCALE * shape[0] ** -0.5
    return shape[0] ** -0.5


def reference_params(params) -> Dict[str, Any]:
    """Program tree -> the plain reference's dict (no copy, no cast beyond
    the seeded-bias mapping's few values)."""
    n = sum(1 for k in params if k.startswith("layers_"))
    layers = []
    for i in range(n):
        lp = params[f"layers_{i}"]
        att, mlp = lp["self_attn"], lp["mlp"]
        layer = {
            "ln1": lp["input_layernorm"]["scale"],
            "ln2": lp["post_attention_layernorm"]["scale"],
            "wq": att["q_proj"]["kernel"],
            "wkva": att["kv_a_proj_with_mqa"]["kernel"],
            "kv_norm": att["kv_a_layernorm"]["scale"],
            "wkvb": att["kv_b_proj"]["kernel"],
            "wo": att["o_proj"]["kernel"]}
        if "gate" in mlp:
            se = mlp["shared_expert"]
            layer.update({
                "router": mlp["gate"]["wg"]["kernel"],
                "bias": mlp["gate"]["e_score_correction_bias"],
                "w_gate": mlp["experts"]["w_gate"],
                "w_up": mlp["experts"]["w_up"],
                "w_down": mlp["experts"]["w_down"],
                "s_gate": se["gate_proj"]["kernel"],
                "s_up": se["up_proj"]["kernel"],
                "s_down": se["down_proj"]["kernel"]})
        else:
            layer.update({"gate": mlp["gate_proj"]["kernel"],
                          "up": mlp["up_proj"]["kernel"],
                          "down": mlp["down_proj"]["kernel"]})
        layers.append(layer)
    return _seeded_bias(
        {"embed": params["embed_tokens"]["embedding"], "layers": layers,
         "norm": params["norm"]["scale"],
         "lm_head": params["lm_head"]["kernel"]}, "bias")


def shapes(hf: Dict[str, Any]) -> Dict[str, int]:
    """Shape facts for ``lib/costs.py``, ``lib/costs_moe.py`` and
    ``lib/costs_mla.py`` (the module doc says how a latent row fills
    them).  ``experts`` is what is HELD here, ``router_width`` the
    published count.  ``matmul_params`` counts what one token multiplies
    by on this chip on average: attention's four projections, and per MoE
    layer the router, the shared experts and ``experts_per_token x held /
    router_width`` routed experts; the dense layers; the lm_head."""
    h, v = hf["hidden_size"], hf["vocab_size"]
    hq = hf["num_attention_heads"]
    rank, nope, rope, vd = hf["kv_lora_rank"], hf["qk_nope_head_dim"], \
        hf["qk_rope_head_dim"], hf["v_head_dim"]
    e, er, k = hf["n_routed_experts"], _router_width(hf), \
        hf["num_experts_per_tok"]
    f, fd = hf["moe_intermediate_size"], hf["intermediate_size"]
    fs = hf["n_shared_experts"] * f
    layers = hf["num_hidden_layers"]
    dense = min(int(hf["first_k_dense_replace"]), layers)
    moe_layers = layers - dense
    attn = h * hq * (nope + rope) + h * (rank + rope) \
        + rank * hq * (nope + vd) + hq * vd * h
    moe_fixed = h * er + 3 * h * fs
    row = -(-(rank + rope) // 128) * 128
    return {"layers": layers, "hidden": h, "q_heads": hq, "kv_heads": 1,
            "head_dim": rank + rope, "vocab": v,
            "kv_lora_rank": rank, "qk_nope_head_dim": nope,
            "qk_rope_head_dim": rope, "v_head_dim": vd,
            "dense_layers": dense, "moe_layers": moe_layers,
            "experts": e, "router_width": er, "experts_per_token": k,
            "expert_width": f,
            "matmul_params": layers * attn + dense * 3 * h * fd
            + moe_layers * (moe_fixed + k * e * 3 * h * f // er) + h * v,
            "total_params": layers * (attn + rank + 2 * h)
            + dense * 3 * h * fd
            + moe_layers * (moe_fixed + er + e * 3 * h * f) + 2 * h * v + h,
            "kv_bytes_per_token": layers * (rank + rope) * 2,
            "kv_row_bytes_per_token": layers * row * 2}
