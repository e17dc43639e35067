"""AFMoE family adapter: from the published ``config.json`` keys
(``model_type: afmoe``, arcee-ai/Trinity-Large-Preview) to the program's
model object (``RaggedAfmoe``), to the plain reference's parameter dict, and
to the shape facts the FLOP/byte functions need.  The only file that knows
both namings.

**The share.**  ``num_experts`` in the configuration file is how many
experts are HELD here (``reduced``); ``router_experts`` beside it is the
published count, the router's width; ``expert_start`` the first held id.

**Two kinds of KV layer.**  ``shapes`` counts them apart (``window_layers``,
``full_layers``, ``window``), and gives the window group's pool as the state
manager sizes it from the ``serve`` group (``win_pool_blocks``: with
``sliding_window - 1 = q x block_size + r``, ``max_ragged_sequence_count x (q
+ 2) + (max_ragged_sequence_count x r + token_budget) // block_size``:
``ragged_manager.py::window_pool_blocks``), which is what ``win_live_pct``
divides by.
``kv_bytes_per_token`` is what a token costs in the pool ``kv_pool_blocks``
counts, for as long as its sequence lives (the global layers);
``kv_band_bytes_per_token`` what it costs besides while it is inside the
band (the window layers, which then let it go).

**Seeded weights.**  Kernels N(0, 1/fan_in), every norm weight 1.  The
embedding is N(0, 1/hidden_size): the model multiplies it by
``sqrt(hidden_size)`` (``mup_enabled``; muP pairs the two), so the stream
starts at RMS 1.  At N(0, 1) it would start at RMS 55 under branches that
the post norms hold at RMS 1 each, five layers would move the logits by a
few percent in all, and ``correct`` would pass with every layer wrong.  No
scaled-residual factor as the other families have: a post norm undoes any
scale on the kernel before it.

**The routed experts' down projections at ``EXPERT_DOWN`` of that.**  This
router gives each of a token's four experts about a quarter of 2.448 = 0.61
of weight, the marginal one as much as the best, and the post norm holds the
FFN branch at RMS 1 whatever it sums: ONE near-tie between the fourth and
the fifth of 256 scores that the bf16 stream decides the other way than the
float32 reference, one of the two experts held here, makes a whole expert
come or go in that row's branch.  Every layer right, the check's 9 rows x 4
routed layers hold such a pair in about one check of two, and the clean
program then reads (v5e, PR 39, max |diff| / max |logit| against the 0.03
allowed): at scale 1, 0.11-0.18 (0.006-0.008 without such a pair); at 0.1,
0.014-0.024 in thirteen of twenty-six; at 0.075, 0.010-0.017 in sixteen of
twenty-six (mean + 4 sd 0.026); at 0.05, at most 0.0137 in thirty-three.
What the check can show of the routed experts is a matter of the seed too:
59% of tokens use none of the 32 held experts in a layer, the 9 checked
rows use a few between them, and a row's worst is what the maximum norm
reads.  The routed experts dropped altogether read 0.025-0.026 at 0.05 and
0.050 at 0.1 on the seeds of the first tables and 0.023 / 0.033 at 0.075 on
two more; weights taken from score + bias 0.023 / 0.159 at 0.075.  So no
scale shows them on every seed while the clean program passes on every
seed: dropped experts are a few experts gone from a row, a near-tie is one,
and the two readings overlap.  ``EXPERT_DOWN`` is 0.075: the clean
program's worst of 26 seeds is 0.57 of the limit (the accepted LFM2 cell
stands at 0.70), and a program that drops or garbles the routed experts
reads over the limit on a share of the seeds, of which a later PR's check
draws two dozen.  The first submission of PR 39 took 0.05, where no seed
shows them; its review asked for them back.  What the check cannot see at
any scale is a fault that moves a few selections (the bias left out of the
selection: 0.014-0.021 at 0.075): on a checked row that IS one expert come
or gone.  The float32 CPU tests see all three
(``tests/unit/test_ragged_afmoe.py``).  The cure on the data side would be
check tokens whose float32 margins between the fourth and the fifth score
exceed the bf16 noise on the 36 checked decisions, and then no scale at all;
the runner draws the check's tokens itself from the seed, which a family
never sees (``PERF.md`` section 7 names the edit).

**The selection bias** ``e_score_correction_bias = BIAS_MEAN + BIAS_STD x
z``, ``z`` the seeded N(0, 1) leaf (``_SeededBias`` applies the mapping to
the served model's parameters on their way in, ``reference_params`` to the
reference's; the program's model is untouched).  The published buffer
``expert_bias`` is trained from zero, to balance the experts' load.  The
spread is 0.02 and not Moonlight's 0.1: at the top-4 of 256 the sigmoid's
scores are squeezed under 1 (the chosen ones lie in 0.90-0.97), so a bias
0.1 higher is worth a logit of +1.3 and the experts with the largest biases
take everything: at 0.1 one expert gets 16 x the mean load and 15 of the 32
held experts get a row in a 1,056-token tick (simulated; call 1's
``gmm_ms_tick`` 3.3 ms a tick where 32 experts' 7.2 GB need 8.8), at 0.02
the heaviest gets 3.5 x and all 32 are streamed, which is what a balanced
deployment does a tick; the bias still changes 22% of the selections.  The
common offset changes no selection (top-k is shift-invariant) and nothing in
a correct program; at -0.92 the chosen experts' ``s + b`` straddle zero, so
a program that lets the bias INTO THE WEIGHTS divides by sums near zero.
"""

from __future__ import annotations

from typing import Any, Dict

REFERENCE = "afmoe"

#: e_score_correction_bias = BIAS_MEAN + BIAS_STD * z (the module doc)
BIAS_MEAN, BIAS_STD = -0.92, 0.02
#: the routed experts' down projections, beside N(0, 1/fan_in)
EXPERT_DOWN = 0.075


def program_config(hf: Dict[str, Any]):
    import jax.numpy as jnp

    from deepspeed_tpu.inference.v2.model_implementations.ragged_afmoe \
        import AfmoeConfig

    return AfmoeConfig(
        vocab_size=hf["vocab_size"], hidden_size=hf["hidden_size"],
        intermediate_size=hf["intermediate_size"],
        moe_intermediate_size=hf["moe_intermediate_size"],
        num_hidden_layers=hf["num_hidden_layers"],
        layer_types=hf["layer_types"],
        global_attn_every_n_layers=hf["global_attn_every_n_layers"],
        sliding_window=hf["sliding_window"],
        num_attention_heads=hf["num_attention_heads"],
        num_key_value_heads=hf["num_key_value_heads"],
        head_dim=hf["head_dim"], num_dense_layers=hf["num_dense_layers"],
        num_experts=_router_width(hf), held_experts=hf["num_experts"],
        expert_start=int(hf.get("expert_start", 0)),
        num_experts_per_tok=hf["num_experts_per_tok"],
        num_shared_experts=hf["num_shared_experts"],
        n_group=hf["n_group"], topk_group=hf["topk_group"],
        score_func=hf["score_func"], route_norm=bool(hf["route_norm"]),
        route_scale=float(hf["route_scale"]),
        mup_enabled=bool(hf["mup_enabled"]),
        rope_theta=float(hf["rope_theta"]), rope_scaling=hf["rope_scaling"],
        rms_norm_eps=hf["rms_norm_eps"],
        max_position_embeddings=hf["max_position_embeddings"],
        tie_word_embeddings=bool(hf["tie_word_embeddings"]),
        dtype=jnp.bfloat16)


def _router_width(hf: Dict[str, Any]) -> int:
    return int(hf.get("router_experts", hf["num_experts"]))


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
    parameters on their way in (inside the step program: 256 values a
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
    from deepspeed_tpu.inference.v2.model_implementations.ragged_afmoe \
        import RaggedAfmoe

    return _SeededBias(RaggedAfmoe(program_config(hf), block_size))


def serve_param_shapes(hf: Dict[str, Any]):
    from deepspeed_tpu.inference.v2.model_implementations.ragged_afmoe \
        import param_shapes

    return param_shapes(program_config(hf))


def init_std(path_names, shape) -> Any:
    """Seeded-weight scale per leaf (the module doc)."""
    leaf = path_names[-1]
    if leaf == "scale":
        return None
    if leaf == "embedding":
        return shape[1] ** -0.5
    if leaf == "e_score_correction_bias":
        return 1.0                  # z of the seeded-bias mapping
    if leaf == "w_down":
        return EXPERT_DOWN * shape[1] ** -0.5
    if leaf in ("w_gate", "w_up"):
        return shape[1] ** -0.5
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
            "ln_in": lp["input_layernorm"]["scale"],
            "ln_post_attn": lp["post_attention_layernorm"]["scale"],
            "ln_pre_mlp": lp["pre_mlp_layernorm"]["scale"],
            "ln_post_mlp": lp["post_mlp_layernorm"]["scale"],
            "wq": att["q_proj"]["kernel"], "wk": att["k_proj"]["kernel"],
            "wv": att["v_proj"]["kernel"], "wg": att["gate_proj"]["kernel"],
            "wo": att["o_proj"]["kernel"],
            "q_norm": att["q_norm"]["scale"],
            "k_norm": att["k_norm"]["scale"]}
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
    ``lib/costs_window.py``.  ``experts`` is what is HELD here,
    ``router_width`` the published count.  ``matmul_params`` counts what
    one token multiplies by on this chip on average: attention's five
    projections, and per MoE layer the router, the shared expert and
    ``experts_per_token x held / router_width`` routed experts; the dense
    layers; the lm_head."""
    h, v = hf["hidden_size"], hf["vocab_size"]
    hq, hkv, d = hf["num_attention_heads"], hf["num_key_value_heads"], \
        hf["head_dim"]
    e, er, k = hf["num_experts"], _router_width(hf), \
        hf["num_experts_per_tok"]
    f, fd = hf["moe_intermediate_size"], hf["intermediate_size"]
    fs = hf["num_shared_experts"] * f
    layers = hf["num_hidden_layers"]
    dense = min(int(hf["num_dense_layers"]), layers)
    moe_layers = layers - dense
    window_layers = sum(t == "sliding_attention" for t in hf["layer_types"])
    attn = 3 * h * hq * d + 2 * h * hkv * d
    moe_fixed = h * er + 3 * h * fs
    out = {"layers": layers, "hidden": h, "q_heads": hq, "kv_heads": hkv,
           "head_dim": d, "vocab": v,
           "window": int(hf["sliding_window"]),
           "window_layers": window_layers,
           "full_layers": layers - window_layers,
           "dense_layers": dense, "moe_layers": moe_layers,
           "experts": e, "router_width": er, "experts_per_token": k,
           "expert_width": f,
           "matmul_params": layers * attn + dense * 3 * h * fd
           + moe_layers * (moe_fixed + k * e * 3 * h * f // er) + h * v,
           "total_params": layers * (attn + 2 * d + 4 * h)
           + dense * 3 * h * fd
           + moe_layers * (moe_fixed + er + e * 3 * h * f) + 2 * h * v + h,
           "kv_bytes_per_token": (layers - window_layers) * 2 * hkv * d * 2,
           "kv_band_bytes_per_token": window_layers * 2 * hkv * d * 2}
    serve = hf.get("serve")
    if serve:
        seqs, bs = int(serve["max_ragged_sequence_count"]), \
            int(serve["block_size"])
        q, r = divmod(out["window"] - 1, bs)
        out["win_pool_blocks"] = seqs * (q + 2) + (
            seqs * r + max(int(serve["token_budget"]), seqs)) // bs
    return out
