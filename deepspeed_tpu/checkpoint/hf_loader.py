"""HuggingFace checkpoint ingestion: safetensors/torch-bin -> flax param
trees for the deepspeed_tpu model families.

Reference analog: ``inference/engine.py:331 load_model_with_checkpoint`` +
the per-architecture weight maps in ``module_inject/containers/`` (~2.3k
LoC of qkv/mlp categorization) + ``runtime/state_dict_factory.py:427``
auto-categorization.  The TPU form is a NAME MAP per architecture: each
entry rewrites one HF tensor name to a path in our param tree plus a
layout transform (torch ``nn.Linear`` stores ``[out, in]``; flax ``Dense``
kernels are ``[in, out]`` — GPT-2's Conv1D is the exception and ships
``[in, out]`` already).  Mixture models additionally STACK per-expert
tensors onto a leading expert axis (our grouped-einsum layout,
moe/sharded_moe.py ``ExpertsFFN``).

Pre-sharded landing: pass ``mesh`` (+ optional ``rules``) and every tensor
is ``jax.device_put`` against its :func:`policy_for` PartitionSpec the
moment it is read — no step ever holds a full unsharded model copy on
device, and the host side reads straight from the (memory-mapped)
safetensors file.

Supported layouts: single ``model.safetensors``, sharded
``model.safetensors.index.json``, and ``pytorch_model.bin`` fallback.
"""

from __future__ import annotations

import dataclasses
import json
import os
import re
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

__all__ = ["load_hf_checkpoint", "config_from_hf", "hf_config",
           "HFLoadError"]


class HFLoadError(RuntimeError):
    pass


# --------------------------------------------------------------------- #
# Tensor iteration over the on-disk layouts
# --------------------------------------------------------------------- #
def _iter_safetensors(path: str):
    from safetensors import safe_open

    try:
        f = safe_open(path, framework="flax")
    except Exception:  # noqa: BLE001 — older safetensors: numpy framework
        f = safe_open(path, framework="np")
    with f:
        for name in f.keys():
            yield name, f.get_tensor(name)


def _iter_torch_bin(path: str):
    import torch

    sd = torch.load(path, map_location="cpu", weights_only=True)
    for name, t in sd.items():
        if t.dtype in (torch.bfloat16, torch.float16):
            yield name, t.to(torch.float32).numpy()
        else:
            yield name, t.numpy()


def iter_checkpoint_tensors(model_path: str):
    """Yield ``(hf_name, array)`` over every tensor in the checkpoint
    directory, resolving sharded safetensors indexes."""
    st = os.path.join(model_path, "model.safetensors")
    idx = os.path.join(model_path, "model.safetensors.index.json")
    bin_ = os.path.join(model_path, "pytorch_model.bin")
    bin_idx = os.path.join(model_path, "pytorch_model.bin.index.json")
    if os.path.exists(idx) or os.path.exists(bin_idx):
        index = idx if os.path.exists(idx) else bin_idx
        with open(index) as f:
            weight_map = json.load(f)["weight_map"]
        files = sorted(set(weight_map.values()))
        it = (_iter_safetensors if index == idx else _iter_torch_bin)
        for fn in files:
            yield from it(os.path.join(model_path, fn))
    elif os.path.exists(st):
        yield from _iter_safetensors(st)
    elif os.path.exists(bin_):
        yield from _iter_torch_bin(bin_)
    else:
        raise HFLoadError(
            f"no model.safetensors(.index.json) or pytorch_model.bin "
            f"under {model_path}")


# --------------------------------------------------------------------- #
# Architecture name maps.  Each rule: (regex, target builder) where the
# builder receives the match and returns (path_tuple, transform) —
# transform "t" = transpose, None = as-is, ("stack", axis_index) = stack
# into the leading expert axis at position axis_index, a callable
# ``(tensor, config) -> array``; a LIST of (path, callable) in the path's
# place fills several leaves from one tensor.
# --------------------------------------------------------------------- #
Rule = Tuple[str, Callable[[re.Match], Tuple[Tuple[str, ...], Any]]]


def _llama_rules() -> List[Rule]:
    return [
        (r"^model\.embed_tokens\.weight$",
         lambda m: (("model", "embed_tokens", "embedding"), None)),
        (r"^model\.layers\.(\d+)\.self_attn\.(q|k|v|o)_proj\.weight$",
         lambda m: (("model", f"layers_{m.group(1)}", "self_attn",
                     f"{m.group(2)}_proj", "kernel"), "t")),
        (r"^model\.layers\.(\d+)\.mlp\.(gate|up|down)_proj\.weight$",
         lambda m: (("model", f"layers_{m.group(1)}", "mlp",
                     f"{m.group(2)}_proj", "kernel"), "t")),
        (r"^model\.layers\.(\d+)\.(input_layernorm|post_attention_layernorm)"
         r"\.weight$",
         lambda m: (("model", f"layers_{m.group(1)}", m.group(2), "scale"),
                    None)),
        (r"^model\.norm\.weight$", lambda m: (("model", "norm", "scale"),
                                              None)),
        (r"^lm_head\.weight$", lambda m: (("lm_head", "kernel"), "t")),
        (r".*rotary_emb\.inv_freq$", lambda m: (None, None)),  # recomputed
    ]


def _flat_moe_backbone_rules() -> List[Rule]:
    # what Mixtral and OLMoE name alike; our tree for both is flat (no
    # "model" wrapper)
    return [
        (r"^model\.embed_tokens\.weight$",
         lambda m: (("embed_tokens", "embedding"), None)),
        (r"^model\.layers\.(\d+)\.self_attn\.(q|k|v|o)_proj\.weight$",
         lambda m: ((f"layers_{m.group(1)}", "self_attn",
                     f"{m.group(2)}_proj", "kernel"), "t")),
        (r"^model\.layers\.(\d+)\.(input_layernorm|post_attention_layernorm)"
         r"\.weight$",
         lambda m: ((f"layers_{m.group(1)}", m.group(2), "scale"), None)),
        (r"^model\.norm\.weight$", lambda m: (("norm", "scale"), None)),
        (r"^lm_head\.weight$", lambda m: (("lm_head", "kernel"), "t")),
    ]


def _moe_path(m, *leaf):
    # the MoE block is moe/layer.py MoE -> deepspeed_moe -> {gate/wg,
    # experts/w_*}
    return (f"layers_{m.group(1)}", "block_sparse_moe", "deepspeed_moe",
            *leaf)


def _mixtral_rules() -> List[Rule]:
    hf2us = {"w1": "w_gate", "w3": "w_up", "w2": "w_down"}
    return _flat_moe_backbone_rules() + [
        (r"^model\.layers\.(\d+)\.block_sparse_moe\.gate\.weight$",
         lambda m: (_moe_path(m, "gate", "wg", "kernel"), "t")),
        (r"^model\.layers\.(\d+)\.block_sparse_moe\.experts\.(\d+)\."
         r"(w1|w2|w3)\.weight$",
         lambda m: (_moe_path(m, "experts", hf2us[m.group(3)]),
                    ("stack", int(m.group(2))))),
    ]


def _olmoe_rules() -> List[Rule]:
    # OLMoE (``model_type: olmoe``) is served and trained by the Mixtral
    # classes, so its tensors land in the Mixtral tree; it differs in its
    # names (``mlp.gate`` / ``mlp.experts.<e>.{gate,up,down}_proj``) and
    # in the q/k RMSNorm scales
    return _flat_moe_backbone_rules() + [
        (r"^model\.layers\.(\d+)\.self_attn\.(q|k)_norm\.weight$",
         lambda m: ((f"layers_{m.group(1)}", "self_attn",
                     f"{m.group(2)}_norm", "scale"), None)),
        (r"^model\.layers\.(\d+)\.mlp\.gate\.weight$",
         lambda m: (_moe_path(m, "gate", "wg", "kernel"), "t")),
        (r"^model\.layers\.(\d+)\.mlp\.experts\.(\d+)\."
         r"(gate|up|down)_proj\.weight$",
         lambda m: (_moe_path(m, "experts", f"w_{m.group(3)}"),
                    ("stack", int(m.group(2))))),
        (r".*rotary_emb\.inv_freq$", lambda m: (None, None)),  # recomputed
    ]


def _qwen3_next_regroup(widths):
    """The published Gated DeltaNet projections interleave their outputs
    PER KEY HEAD (``in_proj_qkvz``: each key head's q, k, then the v and z
    of the value heads it serves; ``in_proj_ba``: its b, then its a);
    ``RaggedQwen3Next`` reads ``q | k | v | z`` and ``b | a`` with all
    heads of one before the next.  ``widths(cfg)`` gives one key head's
    parts.  HF weight [out, in] -> kernel [in, out], regrouped."""
    def tf(w, cfg):
        w = np.asarray(w)
        hk = int(cfg["linear_num_key_heads"])
        parts = np.split(w.reshape(hk, -1, w.shape[-1]),
                         np.cumsum(widths(cfg))[:-1], axis=1)
        return np.concatenate(
            [p.reshape(-1, w.shape[-1]) for p in parts], axis=0).T
    return tf


def _qwen3_next_rules() -> List[Rule]:
    # Qwen3-Next (``model_type: qwen3_next``) -> RaggedQwen3Next's tree
    def layer(m, *leaf):
        return (f"layers_{m.group(1)}", *leaf)

    def ratio(c):
        return int(c["linear_num_value_heads"]) \
            // int(c["linear_num_key_heads"])

    qkvz = _qwen3_next_regroup(lambda c: [
        int(c["linear_key_head_dim"]), int(c["linear_key_head_dim"]),
        ratio(c) * int(c["linear_value_head_dim"]),
        ratio(c) * int(c["linear_value_head_dim"])])
    ba = _qwen3_next_regroup(lambda c: [ratio(c), ratio(c)])
    return _flat_moe_backbone_rules() + [
        (r"^model\.layers\.(\d+)\.self_attn\.(q|k)_norm\.weight$",
         lambda m: (layer(m, "self_attn", f"{m.group(2)}_norm", "scale"),
                    None)),
        (r"^model\.layers\.(\d+)\.linear_attn\.in_proj_qkvz\.weight$",
         lambda m: (layer(m, "linear_attn", "in_proj_qkvz", "kernel"), qkvz)),
        (r"^model\.layers\.(\d+)\.linear_attn\.in_proj_ba\.weight$",
         lambda m: (layer(m, "linear_attn", "in_proj_ba", "kernel"), ba)),
        # [channels, 1, taps] -> [taps, channels], the last tap on the
        # current token either way
        (r"^model\.layers\.(\d+)\.linear_attn\.conv1d\.weight$",
         lambda m: (layer(m, "linear_attn", "conv1d", "kernel"),
                    lambda w, _c: np.asarray(w)[:, 0, :].T)),
        (r"^model\.layers\.(\d+)\.linear_attn\.(A_log|dt_bias)$",
         lambda m: (layer(m, "linear_attn", m.group(2)), None)),
        (r"^model\.layers\.(\d+)\.linear_attn\.norm\.weight$",
         lambda m: (layer(m, "linear_attn", "norm", "scale"), None)),
        (r"^model\.layers\.(\d+)\.linear_attn\.out_proj\.weight$",
         lambda m: (layer(m, "linear_attn", "out_proj", "kernel"), "t")),
        (r"^model\.layers\.(\d+)\.mlp\.gate\.weight$",
         lambda m: (layer(m, "mlp", "gate", "wg", "kernel"), "t")),
        (r"^model\.layers\.(\d+)\.mlp\.experts\.(\d+)\."
         r"(gate|up|down)_proj\.weight$",
         lambda m: (layer(m, "mlp", "experts", f"w_{m.group(3)}"),
                    ("stack", int(m.group(2))))),
        (r"^model\.layers\.(\d+)\.mlp\.shared_expert\."
         r"(gate|up|down)_proj\.weight$",
         lambda m: (layer(m, "mlp", "shared_expert", f"{m.group(2)}_proj",
                          "kernel"), "t")),
        (r"^model\.layers\.(\d+)\.mlp\.shared_expert_gate\.weight$",
         lambda m: (layer(m, "mlp", "shared_expert_gate", "kernel"), "t")),
        # the multi-token-prediction module is not served
        (r"^mtp\..*$", lambda m: (None, None)),
        (r".*rotary_emb\.inv_freq$", lambda m: (None, None)),
    ]


def _deepseek_v3_rope_rows(head_width, first: bool = False,
                           flag: str = "rope_interleave"):
    """The published DeepSeek-V3 family stores the rotary dims of ``q_proj``
    (each head's last ``qk_rope_head_dim`` outputs) and of
    ``kv_a_proj_with_mqa`` (its last ``qk_rope_head_dim`` outputs)
    INTERLEAVED (x0, y0, x1, y1, ...), and its code regroups them to the
    rotate-half layout (x0, x1, ..., y0, y1, ...) before every rotation;
    ``RaggedDeepseekV3`` rotates in that layout, so the regrouping is done
    once, here.  ``head_width(cfg)`` is the width of one block of outputs
    whose LAST rope dims are the rotary ones (``first``: whose FIRST are, an
    indexer head of ``glm_moe_dsa``).  A configuration that sets ``flag``
    false stores them in the rotate-half layout already: a plain
    transpose.  HF weight [out, in] -> kernel [in, out]."""
    def tf(w, cfg):
        w = np.asarray(w)
        if not cfg.get(flag, True):
            return w.T
        rope, width = int(cfg["qk_rope_head_dim"]), head_width(cfg)
        at = 0 if first else width - rope
        order = np.arange(width)
        order[at:at + rope] = at + np.concatenate(
            [np.arange(0, rope, 2), np.arange(1, rope, 2)])
        blocks = w.reshape(-1, width, w.shape[-1])[:, order]
        return blocks.reshape(w.shape).T
    return tf


def _deepseek_v3_rules() -> List[Rule]:
    # DeepSeek-V3 family (``model_type: deepseek_v3``; Moonlight) ->
    # RaggedDeepseekV3's tree
    def layer(m, *leaf):
        return (f"layers_{m.group(1)}", *leaf)

    q_rows = _deepseek_v3_rope_rows(
        lambda c: int(c["qk_nope_head_dim"]) + int(c["qk_rope_head_dim"]))
    kva_rows = _deepseek_v3_rope_rows(
        lambda c: int(c["kv_lora_rank"]) + int(c["qk_rope_head_dim"]))
    backbone = [r for r in _flat_moe_backbone_rules()
                if "self_attn" not in r[0]]
    return backbone + [
        (r"^model\.layers\.(\d+)\.self_attn\.q_proj\.weight$",
         lambda m: (layer(m, "self_attn", "q_proj", "kernel"), q_rows)),
        (r"^model\.layers\.(\d+)\.self_attn\.kv_a_proj_with_mqa\.weight$",
         lambda m: (layer(m, "self_attn", "kv_a_proj_with_mqa", "kernel"),
                    kva_rows)),
        (r"^model\.layers\.(\d+)\.self_attn\.kv_a_layernorm\.weight$",
         lambda m: (layer(m, "self_attn", "kv_a_layernorm", "scale"), None)),
        (r"^model\.layers\.(\d+)\.self_attn\.(kv_b|o)_proj\.weight$",
         lambda m: (layer(m, "self_attn", f"{m.group(2)}_proj", "kernel"),
                    "t")),
        (r"^model\.layers\.(\d+)\.mlp\.(gate|up|down)_proj\.weight$",
         lambda m: (layer(m, "mlp", f"{m.group(2)}_proj", "kernel"), "t")),
        (r"^model\.layers\.(\d+)\.mlp\.gate\.weight$",
         lambda m: (layer(m, "mlp", "gate", "wg", "kernel"), "t")),
        (r"^model\.layers\.(\d+)\.mlp\.gate\.e_score_correction_bias$",
         lambda m: (layer(m, "mlp", "gate", "e_score_correction_bias"),
                    None)),
        (r"^model\.layers\.(\d+)\.mlp\.experts\.(\d+)\."
         r"(gate|up|down)_proj\.weight$",
         lambda m: (layer(m, "mlp", "experts", f"w_{m.group(3)}"),
                    ("stack", int(m.group(2))))),
        (r"^model\.layers\.(\d+)\.mlp\.shared_experts\."
         r"(gate|up|down)_proj\.weight$",
         lambda m: (layer(m, "mlp", "shared_expert", f"{m.group(2)}_proj",
                          "kernel"), "t")),
        (r".*rotary_emb\.inv_freq$", lambda m: (None, None)),
    ]


def _glm_moe_dsa_rules() -> List[Rule]:
    # GLM-5 (``model_type: glm_moe_dsa``) -> RaggedDeepseekV3's tree with a
    # low-rank query and an indexer: the DeepSeek-V3 rules but for the query
    # path, plus the indexer's four leaves.  Both rope layouts
    # (``rope_interleave``: q_b_proj and kv_a_proj_with_mqa, the rotary dims
    # LAST; ``indexer_rope_interleave``: indexer.wq_b and indexer.wk, the
    # rotary dims FIRST) are de-interleaved once, here.  No GLM-5 checkpoint
    # is in the repository: the names are the published ones, tested on a
    # synthetic state dict.
    def layer(m, *leaf):
        return (f"layers_{m.group(1)}", *leaf)

    attn = r"^model\.layers\.(\d+)\.self_attn\."
    q_rows = _deepseek_v3_rope_rows(
        lambda c: int(c["qk_nope_head_dim"]) + int(c["qk_rope_head_dim"]))
    idx_rows = _deepseek_v3_rope_rows(
        lambda c: int(c["index_head_dim"]), first=True,
        flag="indexer_rope_interleave")
    return [
        # the multi-token-prediction layer's own leaves (the layer itself,
        # index num_hidden_layers, is dropped by load_hf_checkpoint)
        (r"^model\.layers\.\d+\.(enorm|hnorm|eh_proj|shared_head)\..*$",
         lambda m: (None, None)),
        (attn + r"q_a_proj\.weight$",
         lambda m: (layer(m, "self_attn", "q_a_proj", "kernel"), "t")),
        (attn + r"q_a_layernorm\.weight$",
         lambda m: (layer(m, "self_attn", "q_a_layernorm", "scale"), None)),
        (attn + r"q_b_proj\.weight$",
         lambda m: (layer(m, "self_attn", "q_b_proj", "kernel"), q_rows)),
        (attn + r"indexer\.wq_b\.weight$",
         lambda m: (layer(m, "self_attn", "indexer", "wq_b", "kernel"),
                    idx_rows)),
        (attn + r"indexer\.wk\.weight$",
         lambda m: (layer(m, "self_attn", "indexer", "wk", "kernel"),
                    idx_rows)),
        (attn + r"indexer\.k_norm\.(weight|bias)$",
         lambda m: (layer(m, "self_attn", "indexer", "k_norm",
                          "scale" if m.group(2) == "weight" else "bias"),
                    None)),
        (attn + r"indexer\.weights_proj\.weight$",
         lambda m: (layer(m, "self_attn", "indexer", "weights_proj",
                          "kernel"), "t")),
    ] + [r for r in _deepseek_v3_rules() if r"q_proj\." not in r[0]]


def _dots3_note_rules() -> List[Rule]:
    # dots3-note (``model_type: dots3_note``; dots3-note-prev's language
    # model) -> RaggedDots3Note's tree: GLM-5's names (a low-rank query, the
    # indexer on full layers) plus ``self_attn.gate_proj`` (the gate a head),
    # with the rope rows of ``q_b_proj`` and ``kv_a_proj_with_mqa``
    # de-interleaved at each layer's OWN widths (``layer_types[i]``: the
    # ``swa_*`` keys on a sliding layer).  The vision tower, the audio
    # encoder and multi-token-prediction layers are NOT served: their
    # tensors are refused by name, not skipped.  No checkpoint is in the
    # repository: the names are the DeepSeek-V3 family's, tested on a seeded
    # tree.
    def layer(m, *leaf):
        return (f"layers_{m.group(1)}", *leaf)

    def own(i: int, head_width):
        """``_deepseek_v3_rope_rows`` at layer ``i``'s kind's widths."""
        def tf(w, cfg):
            pre = "swa_" if cfg["layer_types"][i] == "sliding_attention" \
                else ""
            view = {**cfg, **{k: cfg[pre + k] for k in (
                "qk_nope_head_dim", "qk_rope_head_dim", "kv_lora_rank")}}
            return _deepseek_v3_rope_rows(head_width)(w, view)
        return tf

    def refuse(what):
        def build(m):
            raise HFLoadError(
                f"dots3_note: tensor {m.group(0)!r} belongs to {what}, "
                f"which RaggedDots3Note does not serve (the language model "
                f"alone is): drop it from the checkpoint or load with a "
                f"model that has it")
        return build

    attn = r"^model\.layers\.(\d+)\.self_attn\."
    q_width = lambda c: int(c["qk_nope_head_dim"]) \
        + int(c["qk_rope_head_dim"])
    kva_width = lambda c: int(c["kv_lora_rank"]) + int(c["qk_rope_head_dim"])
    taken = (r"q_b_proj\.", r"kv_a_proj_with_mqa\.", r"q_proj\.")
    return [
        (r"^(model\.)?(visual|vision_tower|vision_model|vision_encoder)\..*$",
         refuse("the vision tower")),
        (r"^(model\.)?(audio_tower|audio_encoder|audio_model|audio)\..*$",
         refuse("the audio encoder")),
        (r"^(model\.)?(mtp|mtp_layers|nextn)\..*$",
         refuse("multi-token prediction")),
        (r"^model\.layers\.\d+\.(enorm|hnorm|eh_proj|shared_head)\..*$",
         refuse("multi-token prediction")),
        (attn + r"q_b_proj\.weight$",
         lambda m: (layer(m, "self_attn", "q_b_proj", "kernel"),
                    own(int(m.group(1)), q_width))),
        (attn + r"kv_a_proj_with_mqa\.weight$",
         lambda m: (layer(m, "self_attn", "kv_a_proj_with_mqa", "kernel"),
                    own(int(m.group(1)), kva_width))),
        (attn + r"gate_proj\.weight$",
         lambda m: (layer(m, "self_attn", "gate_proj", "kernel"), "t")),
    ] + [r for r in _glm_moe_dsa_rules()
         if not any(t in r[0] for t in taken) and "enorm" not in r[0]]


def _longcat_flash_rules() -> List[Rule]:
    # LongCat-Flash (``model_type: longcat_flash``; LongCat-Flash-Omni's
    # language model) -> RaggedLongcatFlash's tree: a published layer keeps
    # its two sub-blocks as module lists (``self_attn.{0,1}``,
    # ``mlps.{0,1}``, ``input_layernorm.{0,1}``,
    # ``post_attention_layernorm.{0,1}``: ``sub_0`` / ``sub_1`` here) and
    # one routed branch (``mlp.router.classifier`` over the 512 experts then
    # the 256 zero-compute outputs, its ``e_score_correction_bias``, and
    # ``mlp.experts.<n>`` for the experts alone: a zero-compute expert has
    # no tensor).  The rope dims of ``q_b_proj`` and ``kv_a_proj_with_mqa``
    # are de-interleaved once, here.  No LongCat-Flash checkpoint is in the
    # repository: the names are the published ones, tested on a synthetic
    # state dict.
    def sub(m, *leaf):
        return (f"layers_{m.group(1)}", f"sub_{m.group(2)}", *leaf)

    def branch(m, *leaf):
        return (f"layers_{m.group(1)}", "mlp", *leaf)

    layer = r"^model\.layers\.(\d+)\."
    attn = layer + r"self_attn\.([01])\."
    q_rows = _deepseek_v3_rope_rows(
        lambda c: int(c["qk_nope_head_dim"]) + int(c["qk_rope_head_dim"]))
    kva_rows = _deepseek_v3_rope_rows(
        lambda c: int(c["kv_lora_rank"]) + int(c["qk_rope_head_dim"]))
    return [r for r in _flat_moe_backbone_rules()
            if r"layers" not in r[0]] + [
        (attn + r"(q_a|kv_b|o)_proj\.weight$",
         lambda m: (sub(m, "self_attn", f"{m.group(3)}_proj", "kernel"),
                    "t")),
        (attn + r"(q_a|kv_a)_layernorm\.weight$",
         lambda m: (sub(m, "self_attn", f"{m.group(3)}_layernorm", "scale"),
                    None)),
        (attn + r"q_b_proj\.weight$",
         lambda m: (sub(m, "self_attn", "q_b_proj", "kernel"), q_rows)),
        (attn + r"kv_a_proj_with_mqa\.weight$",
         lambda m: (sub(m, "self_attn", "kv_a_proj_with_mqa", "kernel"),
                    kva_rows)),
        (layer + r"mlps\.([01])\.(gate|up|down)_proj\.weight$",
         lambda m: (sub(m, "mlp", f"{m.group(3)}_proj", "kernel"), "t")),
        (layer + r"(input_layernorm|post_attention_layernorm)\.([01])"
         r"\.weight$",
         lambda m: ((f"layers_{m.group(1)}", f"sub_{m.group(3)}",
                     m.group(2), "scale"), None)),
        (layer + r"mlp\.router\.classifier\.weight$",
         lambda m: (branch(m, "gate", "wg", "kernel"), "t")),
        (layer + r"mlp\.router\.e_score_correction_bias$",
         lambda m: (branch(m, "gate", "e_score_correction_bias"), None)),
        (layer + r"mlp\.experts\.(\d+)\.(gate|up|down)_proj\.weight$",
         lambda m: (branch(m, "experts", f"w_{m.group(3)}"),
                    ("stack", int(m.group(2))))),
        (r".*rotary_emb\.inv_freq$", lambda m: (None, None)),
    ]


def _lfm2_moe_rules() -> List[Rule]:
    # LFM2-MoE (``model_type: lfm2_moe``; LFM2-24B-A2B) -> RaggedLfm2's tree.
    # The head is tied to the embedding: a checkpoint's ``lm_head.weight``
    # (safetensors writes tied tensors once, torch.save twice) is skipped.
    def layer(m, *leaf):
        return (f"layers_{m.group(1)}", *leaf)

    ffn = {"w1": "gate", "w3": "up", "w2": "down"}
    return [
        (r"^model\.embed_tokens\.weight$",
         lambda m: (("embed_tokens", "embedding"), None)),
        (r"^model\.embedding_norm\.weight$",
         lambda m: (("norm", "scale"), None)),
        (r"^lm_head\.weight$", lambda m: (None, None)),
        (r"^model\.layers\.(\d+)\.(operator_norm|ffn_norm)\.weight$",
         lambda m: (layer(m, m.group(2), "scale"), None)),
        # in_proj's outputs are B | C | x in that order, as RaggedLfm2 reads
        (r"^model\.layers\.(\d+)\.conv\.(in_proj|out_proj)\.weight$",
         lambda m: (layer(m, "conv", m.group(2), "kernel"), "t")),
        # [channels, 1, taps] -> [taps, channels], the last tap on the
        # current token either way
        (r"^model\.layers\.(\d+)\.conv\.conv\.weight$",
         lambda m: (layer(m, "conv", "conv1d", "kernel"),
                    lambda w, _c: np.asarray(w)[:, 0, :].T)),
        (r"^model\.layers\.(\d+)\.self_attn\.(q|k|v)_proj\.weight$",
         lambda m: (layer(m, "self_attn", f"{m.group(2)}_proj", "kernel"),
                    "t")),
        (r"^model\.layers\.(\d+)\.self_attn\.out_proj\.weight$",
         lambda m: (layer(m, "self_attn", "o_proj", "kernel"), "t")),
        (r"^model\.layers\.(\d+)\.self_attn\.(q|k)_layernorm\.weight$",
         lambda m: (layer(m, "self_attn", f"{m.group(2)}_norm", "scale"),
                    None)),
        (r"^model\.layers\.(\d+)\.feed_forward\.(w1|w2|w3)\.weight$",
         lambda m: (layer(m, "mlp", f"{ffn[m.group(2)]}_proj", "kernel"),
                    "t")),
        (r"^model\.layers\.(\d+)\.feed_forward\.gate\.weight$",
         lambda m: (layer(m, "mlp", "gate", "wg", "kernel"), "t")),
        (r"^model\.layers\.(\d+)\.feed_forward\.expert_bias$",
         lambda m: (layer(m, "mlp", "gate", "e_score_correction_bias"),
                    None)),
        (r"^model\.layers\.(\d+)\.feed_forward\.experts\.(\d+)\."
         r"(w1|w2|w3)\.weight$",
         lambda m: (layer(m, "mlp", "experts", f"w_{ffn[m.group(3)]}"),
                    ("stack", int(m.group(2))))),
        (r".*rotary_emb\.inv_freq$", lambda m: (None, None)),
    ]


def _gpt2_rules() -> List[Rule]:
    # GPT-2 Conv1D weights are already [in, out] — no transpose
    return [
        (r"^(transformer\.)?wte\.weight$",
         lambda m: (("wte", "embedding"), None)),
        (r"^(transformer\.)?wpe\.weight$",
         lambda m: (("wpe", "embedding"), None)),
        (r"^(transformer\.)?h\.(\d+)\.(ln_1|ln_2)\.(weight|bias)$",
         lambda m: ((f"h_{m.group(2)}", m.group(3),
                     "scale" if m.group(4) == "weight" else "bias"), None)),
        (r"^(transformer\.)?h\.(\d+)\.attn\.c_attn\.(weight|bias)$",
         lambda m: ((f"h_{m.group(2)}", "c_attn",
                     "kernel" if m.group(3) == "weight" else "bias"), None)),
        (r"^(transformer\.)?h\.(\d+)\.attn\.c_proj\.(weight|bias)$",
         lambda m: ((f"h_{m.group(2)}", "attn_out",
                     "kernel" if m.group(3) == "weight" else "bias"), None)),
        (r"^(transformer\.)?h\.(\d+)\.mlp\.(c_fc|c_proj)\.(weight|bias)$",
         lambda m: ((f"h_{m.group(2)}", m.group(3),
                     "kernel" if m.group(4) == "weight" else "bias"), None)),
        (r"^(transformer\.)?ln_f\.(weight|bias)$",
         lambda m: (("ln_f",
                     "scale" if m.group(2) == "weight" else "bias"), None)),
        (r"^lm_head\.weight$", lambda m: (None, None)),  # tied to wte
        (r".*\.attn\.(bias|masked_bias)$", lambda m: (None, None)),
    ]


def _opt_rules() -> List[Rule]:
    def leaf(kind):  # weight->kernel (transposed), bias->bias
        return ("kernel", "t") if kind == "weight" else ("bias", None)

    def lin(m):
        name, t = leaf(m.group(3))
        return ((f"layers_{m.group(1)}", "self_attn", m.group(2), name), t)

    def fc(m):
        name, t = leaf(m.group(3))
        return ((f"layers_{m.group(1)}", m.group(2), name), t)

    return [
        (r"^(model\.decoder|decoder)\.embed_tokens\.weight$",
         lambda m: (("embed_tokens", "embedding"), None)),
        (r"^(model\.decoder|decoder)\.embed_positions\.weight$",
         lambda m: (("embed_positions", "embedding"), None)),
        (r"^(?:model\.decoder|decoder)\.layers\.(\d+)\.self_attn\."
         r"(q_proj|k_proj|v_proj|out_proj)\.(weight|bias)$", lin),
        (r"^(?:model\.decoder|decoder)\.layers\.(\d+)\.(fc1|fc2)\."
         r"(weight|bias)$", fc),
        (r"^(?:model\.decoder|decoder)\.layers\.(\d+)\."
         r"(?:self_attn_layer_norm)\.(weight|bias)$",
         lambda m: ((f"layers_{m.group(1)}", "self_attn_layer_norm",
                     "scale" if m.group(2) == "weight" else "bias"), None)),
        (r"^(?:model\.decoder|decoder)\.layers\.(\d+)\.final_layer_norm\."
         r"(weight|bias)$",
         lambda m: ((f"layers_{m.group(1)}", "final_layer_norm",
                     "scale" if m.group(2) == "weight" else "bias"), None)),
        (r"^(?:model\.decoder|decoder)\.final_layer_norm\.(weight|bias)$",
         lambda m: (("final_layer_norm",
                     "scale" if m.group(1) == "weight" else "bias"), None)),
        (r"^lm_head\.weight$", lambda m: (None, None)),  # tied
    ]


def _falcon_rules() -> List[Rule]:
    def ln(m):
        return ((f"h_{m.group(1)}", "input_layernorm",
                 "scale" if m.group(2) == "weight" else "bias"), None)

    def lin(m):
        name = "kernel" if m.group(4) == "weight" else "bias"
        return ((f"h_{m.group(1)}", m.group(2), m.group(3), name),
                "t" if name == "kernel" else None)

    return [
        (r"^(transformer\.)?word_embeddings\.weight$",
         lambda m: (("word_embeddings", "embedding"), None)),
        (r"^(?:transformer\.)?h\.(\d+)\.input_layernorm\.(weight|bias)$",
         ln),
        (r"^(?:transformer\.)?h\.(\d+)\.(self_attention)\."
         r"(query_key_value|dense)\.(weight|bias)$", lin),
        (r"^(?:transformer\.)?h\.(\d+)\.(mlp)\."
         r"(dense_h_to_4h|dense_4h_to_h)\.(weight|bias)$", lin),
        (r"^(transformer\.)?ln_f\.(weight|bias)$",
         lambda m: (("ln_f",
                     "scale" if m.group(2) == "weight" else "bias"), None)),
        (r"^lm_head\.weight$", lambda m: (None, None)),  # tied
    ]


def _ln(path_fn):
    """LayerNorm rule helper: weight->scale, bias->bias (both as-is)."""
    def build(m):
        *head, kind = path_fn(m)
        return (tuple(head) + ("scale" if kind == "weight" else "bias",),
                None)
    return build


def _dense(path_fn):
    """Linear rule helper: weight->kernel (transposed), bias->bias."""
    def build(m):
        *head, kind = path_fn(m)
        if kind == "weight":
            return tuple(head) + ("kernel",), "t"
        return tuple(head) + ("bias",), None
    return build


def _bloom_rules() -> List[Rule]:
    return [
        (r"^(?:transformer\.)?word_embeddings\.weight$",
         lambda m: (("word_embeddings", "embedding"), None)),
        (r"^(?:transformer\.)?word_embeddings_layernorm\.(weight|bias)$",
         _ln(lambda m: ("word_embeddings_layernorm", m.group(1)))),
        (r"^(?:transformer\.)?h\.(\d+)\."
         r"(input_layernorm|post_attention_layernorm)\.(weight|bias)$",
         _ln(lambda m: (f"h_{m.group(1)}", m.group(2), m.group(3)))),
        (r"^(?:transformer\.)?h\.(\d+)\.self_attention\."
         r"(query_key_value|dense)\.(weight|bias)$",
         _dense(lambda m: (f"h_{m.group(1)}", "self_attention",
                           m.group(2), m.group(3)))),
        (r"^(?:transformer\.)?h\.(\d+)\.mlp\."
         r"(dense_h_to_4h|dense_4h_to_h)\.(weight|bias)$",
         _dense(lambda m: (f"h_{m.group(1)}", "mlp", m.group(2),
                           m.group(3)))),
        (r"^(?:transformer\.)?ln_f\.(weight|bias)$",
         _ln(lambda m: ("ln_f", m.group(1)))),
        (r"^lm_head\.weight$", lambda m: (None, None)),  # tied
    ]


def _gptj_rules() -> List[Rule]:
    return [
        (r"^(?:transformer\.)?wte\.weight$",
         lambda m: (("wte", "embedding"), None)),
        (r"^(?:transformer\.)?h\.(\d+)\.ln_1\.(weight|bias)$",
         _ln(lambda m: (f"h_{m.group(1)}", "ln_1", m.group(2)))),
        (r"^(?:transformer\.)?h\.(\d+)\.attn\."
         r"(q_proj|k_proj|v_proj|out_proj)\.weight$",
         _dense(lambda m: (f"h_{m.group(1)}", "attn", m.group(2),
                           "weight"))),
        (r"^(?:transformer\.)?h\.(\d+)\.mlp\.(fc_in|fc_out)\."
         r"(weight|bias)$",
         _dense(lambda m: (f"h_{m.group(1)}", m.group(2), m.group(3)))),
        (r"^(?:transformer\.)?ln_f\.(weight|bias)$",
         _ln(lambda m: ("ln_f", m.group(1)))),
        (r"^lm_head\.(weight|bias)$",
         _dense(lambda m: ("lm_head", m.group(1)))),
        (r".*\.attn\.(bias|masked_bias)$", lambda m: (None, None)),
    ]


def _gptneox_rules() -> List[Rule]:
    return [
        (r"^gpt_neox\.embed_in\.weight$",
         lambda m: (("embed_in", "embedding"), None)),
        (r"^gpt_neox\.layers\.(\d+)\."
         r"(input_layernorm|post_attention_layernorm)\.(weight|bias)$",
         _ln(lambda m: (f"layers_{m.group(1)}", m.group(2), m.group(3)))),
        (r"^gpt_neox\.layers\.(\d+)\.attention\."
         r"(query_key_value|dense)\.(weight|bias)$",
         _dense(lambda m: (f"layers_{m.group(1)}", "attention",
                           m.group(2), m.group(3)))),
        (r"^gpt_neox\.layers\.(\d+)\.mlp\."
         r"(dense_h_to_4h|dense_4h_to_h)\.(weight|bias)$",
         _dense(lambda m: (f"layers_{m.group(1)}", "mlp", m.group(2),
                           m.group(3)))),
        (r"^gpt_neox\.final_layer_norm\.(weight|bias)$",
         _ln(lambda m: ("final_layer_norm", m.group(1)))),
        (r"^embed_out\.weight$",
         lambda m: (("embed_out", "kernel"), "t")),
        (r"^gpt_neox\.layers\.\d+\.attention\."
         r"(bias|masked_bias|rotary_emb\.inv_freq)$",
         lambda m: (None, None)),
    ]


def _bert_rules() -> List[Rule]:
    return [
        (r"^(?:bert\.)?embeddings\.(word_embeddings|position_embeddings|"
         r"token_type_embeddings)\.weight$",
         lambda m: (("embeddings", m.group(1), "embedding"), None)),
        (r"^(?:bert\.)?embeddings\.LayerNorm\.(weight|bias)$",
         _ln(lambda m: ("embeddings", "layer_norm", m.group(1)))),
        (r"^(?:bert\.)?encoder\.layer\.(\d+)\.attention\.self\."
         r"(query|key|value)\.(weight|bias)$",
         _dense(lambda m: ("encoder", f"layer_{m.group(1)}", "attention",
                           "self", m.group(2), m.group(3)))),
        (r"^(?:bert\.)?encoder\.layer\.(\d+)\.attention\.output\.dense\."
         r"(weight|bias)$",
         _dense(lambda m: ("encoder", f"layer_{m.group(1)}", "attention",
                           "output", "dense", m.group(2)))),
        (r"^(?:bert\.)?encoder\.layer\.(\d+)\.attention\.output\."
         r"LayerNorm\.(weight|bias)$",
         _ln(lambda m: ("encoder", f"layer_{m.group(1)}", "attention",
                        "output", "layer_norm", m.group(2)))),
        (r"^(?:bert\.)?encoder\.layer\.(\d+)\.intermediate\.dense\."
         r"(weight|bias)$",
         _dense(lambda m: ("encoder", f"layer_{m.group(1)}",
                           "intermediate", "dense", m.group(2)))),
        (r"^(?:bert\.)?encoder\.layer\.(\d+)\.output\.dense\."
         r"(weight|bias)$",
         _dense(lambda m: ("encoder", f"layer_{m.group(1)}", "output",
                           "dense", m.group(2)))),
        (r"^(?:bert\.)?encoder\.layer\.(\d+)\.output\.LayerNorm\."
         r"(weight|bias)$",
         _ln(lambda m: ("encoder", f"layer_{m.group(1)}", "output",
                        "layer_norm", m.group(2)))),
        (r"^(?:bert\.)?pooler\.dense\.(weight|bias)$",
         _dense(lambda m: ("pooler", "dense", m.group(1)))),
        (r"^(?:bert\.)?embeddings\.position_ids$",
         lambda m: (None, None)),
    ]


def _afmoe_rules() -> List[Rule]:
    # AFMoE (``model_type: afmoe``; Arcee Trinity) -> RaggedAfmoe's tree.
    # Two ``gate_proj`` a layer: the attention's output gate and the
    # SwiGLU's; the router is ``mlp.router.gate`` and its selection bias the
    # buffer ``mlp.expert_bias``; four norms a layer.
    def layer(m, *leaf):
        return (f"layers_{m.group(1)}", *leaf)

    return _flat_moe_backbone_rules() + [
        (r"^model\.layers\.(\d+)\.(pre_mlp_layernorm|post_mlp_layernorm)"
         r"\.weight$", lambda m: (layer(m, m.group(2), "scale"), None)),
        (r"^model\.layers\.(\d+)\.self_attn\.gate_proj\.weight$",
         lambda m: (layer(m, "self_attn", "gate_proj", "kernel"), "t")),
        (r"^model\.layers\.(\d+)\.self_attn\.(q|k)_norm\.weight$",
         lambda m: (layer(m, "self_attn", f"{m.group(2)}_norm", "scale"),
                    None)),
        (r"^model\.layers\.(\d+)\.mlp\.(gate|up|down)_proj\.weight$",
         lambda m: (layer(m, "mlp", f"{m.group(2)}_proj", "kernel"), "t")),
        (r"^model\.layers\.(\d+)\.mlp\.router\.gate\.weight$",
         lambda m: (layer(m, "mlp", "gate", "wg", "kernel"), "t")),
        (r"^model\.layers\.(\d+)\.mlp\.expert_bias$",
         lambda m: (layer(m, "mlp", "gate", "e_score_correction_bias"),
                    None)),
        (r"^model\.layers\.(\d+)\.mlp\.experts\.(\d+)\."
         r"(gate|up|down)_proj\.weight$",
         lambda m: (layer(m, "mlp", "experts", f"w_{m.group(3)}"),
                    ("stack", int(m.group(2))))),
        (r"^model\.layers\.(\d+)\.mlp\.shared_experts\."
         r"(gate|up|down)_proj\.weight$",
         lambda m: (layer(m, "mlp", "shared_expert", f"{m.group(2)}_proj",
                          "kernel"), "t")),
        (r".*rotary_emb\.inv_freq$", lambda m: (None, None)),
    ]


def _ouro_rules() -> List[Rule]:
    # Ouro (``model_type: ouro``; a looped stack) -> RaggedOuro's tree: the
    # Llama names, flat, with a second norm after each branch and the exit
    # gate beside the final norm.
    def layer(m, *leaf):
        return (f"layers_{m.group(1)}", *leaf)

    return _flat_moe_backbone_rules() + [
        (r"^model\.layers\.(\d+)\.(input_layernorm_2|"
         r"post_attention_layernorm_2)\.weight$",
         lambda m: (layer(m, m.group(2), "scale"), None)),
        (r"^model\.layers\.(\d+)\.mlp\.(gate|up|down)_proj\.weight$",
         lambda m: (layer(m, "mlp", f"{m.group(2)}_proj", "kernel"), "t")),
        (r"^model\.early_exit_gate\.weight$",
         lambda m: (("early_exit_gate", "kernel"), "t")),
        (r"^model\.early_exit_gate\.bias$",
         lambda m: (("early_exit_gate", "bias"), None)),
        (r".*rotary_emb\.inv_freq$", lambda m: (None, None)),
    ]


def _jamba_rules() -> List[Rule]:
    # Jamba (``model_type: jamba``, the dense sibling: ``num_experts`` 1) ->
    # RaggedJamba's tree.  The head is tied to the embedding (a checkpoint's
    # ``lm_head.weight`` is skipped); ``A_log`` is stored [Di, N] and read
    # [N, Di], the layout of the state it decays.
    def layer(m, *leaf):
        return (f"layers_{m.group(1)}", *leaf)

    return [
        (r"^model\.embed_tokens\.weight$",
         lambda m: (("embed_tokens", "embedding"), None)),
        (r"^model\.final_layernorm\.weight$",
         lambda m: (("final_layernorm", "scale"), None)),
        (r"^lm_head\.weight$", lambda m: (None, None)),
        (r"^model\.layers\.(\d+)\.(input_layernorm|pre_ff_layernorm)"
         r"\.weight$", lambda m: (layer(m, m.group(2), "scale"), None)),
        (r"^model\.layers\.(\d+)\.mamba\.(in_proj|x_proj|out_proj)\.weight$",
         lambda m: (layer(m, "mamba", m.group(2), "kernel"), "t")),
        (r"^model\.layers\.(\d+)\.mamba\.dt_proj\.(weight|bias)$",
         _dense(lambda m: (*layer(m, "mamba", "dt_proj"), m.group(2)))),
        # [channels, 1, taps] -> [taps, channels], the last tap on the
        # current token either way
        (r"^model\.layers\.(\d+)\.mamba\.conv1d\.weight$",
         lambda m: (layer(m, "mamba", "conv1d", "kernel"),
                    lambda w, _c: np.asarray(w)[:, 0, :].T)),
        (r"^model\.layers\.(\d+)\.mamba\.conv1d\.bias$",
         lambda m: (layer(m, "mamba", "conv1d", "bias"), None)),
        (r"^model\.layers\.(\d+)\.mamba\.A_log$",
         lambda m: (layer(m, "mamba", "A_log"), "t")),
        (r"^model\.layers\.(\d+)\.mamba\.D$",
         lambda m: (layer(m, "mamba", "D"), None)),
        (r"^model\.layers\.(\d+)\.mamba\.(dt|b|c)_layernorm\.weight$",
         lambda m: (layer(m, "mamba", f"{m.group(2)}_layernorm", "scale"),
                    None)),
        (r"^model\.layers\.(\d+)\.self_attn\.(q|k|v|o)_proj\.weight$",
         lambda m: (layer(m, "self_attn", f"{m.group(2)}_proj", "kernel"),
                    "t")),
        (r"^model\.layers\.(\d+)\.feed_forward\.(gate|up|down)_proj"
         r"\.weight$",
         lambda m: (layer(m, "mlp", f"{m.group(2)}_proj", "kernel"), "t")),
    ]


def _granitemoehybrid_rules() -> List[Rule]:
    # Granite-4.0-H (``model_type: granitemoehybrid``) ->
    # RaggedGraniteMoeHybrid's tree.  The head is tied to the embedding (a
    # checkpoint's ``lm_head.weight`` is skipped); ``mamba.in_proj``'s
    # columns are ``z | xBC | dt`` as published; the experts' and the shared
    # expert's ``input_linear`` stack the gate's rows over the up
    # projection's (``chunk(2)`` of the output: the FIRST half is
    # activated), so ONE tensor fills two leaves.
    def layer(m, *leaf):
        return (f"layers_{m.group(1)}", *leaf)

    def half(which: int):       # [..., 2 F, H] -> [..., H, F]
        def tf(w, _c):
            w = np.asarray(w)
            f = w.shape[-2] // 2
            return np.swapaxes(w[..., which * f:(which + 1) * f, :], -1, -2)
        return tf

    return [
        (r"^model\.embed_tokens\.weight$",
         lambda m: (("embed_tokens", "embedding"), None)),
        (r"^model\.norm\.weight$", lambda m: (("norm", "scale"), None)),
        (r"^lm_head\.weight$", lambda m: (None, None)),
        (r"^model\.layers\.(\d+)\.(input_layernorm|post_attention_layernorm)"
         r"\.weight$", lambda m: (layer(m, m.group(2), "scale"), None)),
        (r"^model\.layers\.(\d+)\.mamba\.(in_proj|out_proj)\.weight$",
         lambda m: (layer(m, "mamba", m.group(2), "kernel"), "t")),
        # [channels, 1, taps] -> [taps, channels], the last tap on the
        # current token either way
        (r"^model\.layers\.(\d+)\.mamba\.conv1d\.weight$",
         lambda m: (layer(m, "mamba", "conv1d", "kernel"),
                    lambda w, _c: np.asarray(w)[:, 0, :].T)),
        (r"^model\.layers\.(\d+)\.mamba\.conv1d\.bias$",
         lambda m: (layer(m, "mamba", "conv1d", "bias"), None)),
        (r"^model\.layers\.(\d+)\.mamba\.(dt_bias|A_log|D)$",
         lambda m: (layer(m, "mamba", m.group(2)), None)),
        (r"^model\.layers\.(\d+)\.mamba\.norm\.weight$",
         lambda m: (layer(m, "mamba", "norm", "scale"), None)),
        (r"^model\.layers\.(\d+)\.self_attn\.(q|k|v|o)_proj\.weight$",
         lambda m: (layer(m, "self_attn", f"{m.group(2)}_proj", "kernel"),
                    "t")),
        # [E, 2 x 768, 4096] -> w_gate, w_up [E, 4096, 768]
        (r"^model\.layers\.(\d+)\.block_sparse_moe\.input_linear\.weight$",
         lambda m: ([(layer(m, "block_sparse_moe", "experts", "w_gate"),
                      half(0)),
                     (layer(m, "block_sparse_moe", "experts", "w_up"),
                      half(1))], None)),
        # [E, 4096, 768] -> w_down [E, 768, 4096]
        (r"^model\.layers\.(\d+)\.block_sparse_moe\.output_linear\.weight$",
         lambda m: (layer(m, "block_sparse_moe", "experts", "w_down"),
                    lambda w, _c: np.swapaxes(np.asarray(w), -1, -2))),
        (r"^model\.layers\.(\d+)\.block_sparse_moe\.router\.layer\.weight$",
         lambda m: (layer(m, "block_sparse_moe", "gate", "wg", "kernel"),
                    "t")),
        (r"^model\.layers\.(\d+)\.shared_mlp\.input_linear\.weight$",
         lambda m: ([(layer(m, "block_sparse_moe", "shared_expert",
                            "gate_proj", "kernel"), half(0)),
                     (layer(m, "block_sparse_moe", "shared_expert",
                            "up_proj", "kernel"), half(1))], None)),
        (r"^model\.layers\.(\d+)\.shared_mlp\.output_linear\.weight$",
         lambda m: (layer(m, "block_sparse_moe", "shared_expert",
                          "down_proj", "kernel"), "t")),
    ]


_ARCH_RULES: Dict[str, Callable[[], List[Rule]]] = {
    "llama": _llama_rules,
    "mistral": _llama_rules,     # same architecture/serialization
    "internlm": _llama_rules,
    "mixtral": _mixtral_rules,
    "olmoe": _olmoe_rules,
    "qwen3_next": _qwen3_next_rules,
    "deepseek_v3": _deepseek_v3_rules,
    "glm_moe_dsa": _glm_moe_dsa_rules,
    "longcat_flash": _longcat_flash_rules,
    "dots3_note": _dots3_note_rules,
    "lfm2_moe": _lfm2_moe_rules,
    "afmoe": _afmoe_rules,
    "ouro": _ouro_rules,
    "jamba": _jamba_rules,
    "granitemoehybrid": _granitemoehybrid_rules,
    "gpt2": _gpt2_rules,
    "opt": _opt_rules,
    "falcon": _falcon_rules,
    "bloom": _bloom_rules,
    "gptj": _gptj_rules,
    "gpt_neox": _gptneox_rules,
    "gptneox": _gptneox_rules,
    "bert": _bert_rules,
}


# --------------------------------------------------------------------- #
# Config translation
# --------------------------------------------------------------------- #
def hf_config(model_path: str) -> Dict[str, Any]:
    with open(os.path.join(model_path, "config.json")) as f:
        return json.load(f)


def config_from_hf(model_path: str, dtype: Any = None):
    """Build the matching deepspeed_tpu model config from a HF
    ``config.json``.  Returns ``(architecture, config)``."""
    import jax.numpy as jnp

    cfg = hf_config(model_path)
    arch = cfg.get("model_type", "").lower()
    dt = dtype if dtype is not None else jnp.bfloat16
    if arch in ("llama", "mistral", "internlm"):
        from deepspeed_tpu.models.llama import LlamaConfig

        return arch, LlamaConfig(
            vocab_size=cfg["vocab_size"],
            hidden_size=cfg["hidden_size"],
            intermediate_size=cfg["intermediate_size"],
            num_hidden_layers=cfg["num_hidden_layers"],
            num_attention_heads=cfg["num_attention_heads"],
            num_key_value_heads=cfg.get(
                "num_key_value_heads", cfg["num_attention_heads"]),
            max_position_embeddings=cfg["max_position_embeddings"],
            rms_norm_eps=cfg.get("rms_norm_eps", 1e-5),
            rope_theta=cfg.get("rope_theta", 10000.0),
            tie_word_embeddings=cfg.get("tie_word_embeddings", False),
            sliding_window=cfg.get("sliding_window"),
            dtype=dt)
    if arch == "mixtral":
        from deepspeed_tpu.models.mixtral import MixtralConfig

        return arch, MixtralConfig(
            vocab_size=cfg["vocab_size"],
            hidden_size=cfg["hidden_size"],
            intermediate_size=cfg["intermediate_size"],
            num_hidden_layers=cfg["num_hidden_layers"],
            num_attention_heads=cfg["num_attention_heads"],
            num_key_value_heads=cfg.get(
                "num_key_value_heads", cfg["num_attention_heads"]),
            max_position_embeddings=cfg["max_position_embeddings"],
            rms_norm_eps=cfg.get("rms_norm_eps", 1e-5),
            rope_theta=cfg.get("rope_theta", 10000.0),
            num_local_experts=cfg.get("num_local_experts", 8),
            num_experts_per_tok=cfg.get("num_experts_per_tok", 2),
            dtype=dt)
    if arch == "olmoe":
        from deepspeed_tpu.models.mixtral import MixtralConfig

        if cfg.get("clip_qkv") is not None or cfg.get("attention_bias") \
                or cfg.get("rope_scaling") is not None \
                or cfg.get("tie_word_embeddings"):
            raise HFLoadError(
                "olmoe: clip_qkv, attention_bias, rope_scaling and a tied "
                "head are not implemented (OLMoE-1B-7B sets none of them)")
        return arch, MixtralConfig.olmoe_1b_7b(
            vocab_size=cfg["vocab_size"],
            hidden_size=cfg["hidden_size"],
            intermediate_size=cfg["intermediate_size"],
            num_hidden_layers=cfg["num_hidden_layers"],
            num_attention_heads=cfg["num_attention_heads"],
            num_key_value_heads=cfg.get(
                "num_key_value_heads", cfg["num_attention_heads"]),
            max_position_embeddings=cfg["max_position_embeddings"],
            rms_norm_eps=cfg.get("rms_norm_eps", 1e-5),
            rope_theta=float(cfg.get("rope_theta", 10000.0)),
            num_local_experts=cfg["num_experts"],
            num_experts_per_tok=cfg["num_experts_per_tok"],
            norm_topk_prob=cfg.get("norm_topk_prob", False),
            dtype=dt)
    if arch == "qwen3_next":
        from deepspeed_tpu.inference.v2.model_implementations. \
            ragged_qwen3_next import Qwen3NextConfig

        if cfg.get("rope_scaling") is not None or cfg.get("attention_bias") \
                or cfg.get("use_sliding_window") or cfg.get("mlp_only_layers") \
                or cfg.get("decoder_sparse_step", 1) != 1 \
                or cfg.get("tie_word_embeddings"):
            raise HFLoadError(
                "qwen3_next: rope_scaling, attention_bias, a sliding "
                "window, dense-only layers and a tied head are not "
                "implemented (Qwen3-Next-80B-A3B sets none of them)")
        fields = {f.name for f in dataclasses.fields(Qwen3NextConfig)} \
            - {"dtype"}
        return arch, Qwen3NextConfig(
            **{k: v for k, v in cfg.items() if k in fields}, dtype=dt)
    if arch == "deepseek_v3":
        from deepspeed_tpu.inference.v2.model_implementations. \
            ragged_deepseek_v3 import DeepseekV3Config

        if cfg.get("rope_scaling") is not None or cfg.get("attention_bias") \
                or cfg.get("tie_word_embeddings") \
                or cfg.get("num_nextn_predict_layers"):
            raise HFLoadError(
                "deepseek_v3: rope_scaling (and its scaled softmax factor), "
                "attention_bias, a tied head and a multi-token-prediction "
                "module are not implemented (Moonlight-16B-A3B sets none "
                "of them)")
        fields = {f.name for f in dataclasses.fields(DeepseekV3Config)} \
            - {"dtype"}
        # (n_group > 1, q_lora_rank, another scoring_func: the config
        # refuses each by name)
        return arch, DeepseekV3Config(
            **{k: v for k, v in cfg.items() if k in fields}, dtype=dt)
    if arch == "glm_moe_dsa":
        from deepspeed_tpu.inference.v2.model_implementations. \
            ragged_deepseek_v3 import DeepseekV3Config

        rope = cfg.get("rope_parameters") or {}
        if rope.get("rope_type", "default") != "default" \
                or cfg.get("rope_scaling") is not None \
                or cfg.get("attention_bias") \
                or cfg.get("tie_word_embeddings"):
            raise HFLoadError(
                "glm_moe_dsa: a scaled rotary embedding, attention_bias "
                "and a tied head are not implemented (GLM-5 sets none of "
                "them)")
        fields = {f.name for f in dataclasses.fields(DeepseekV3Config)} \
            - {"dtype", "rope_theta"}
        # (num_nextn_predict_layers: the multi-token-prediction layer is
        # not on the path to the main head's logits; load_hf_checkpoint
        # drops it)
        return arch, DeepseekV3Config(
            **{k: v for k, v in cfg.items() if k in fields},
            rope_theta=float(rope.get("rope_theta",
                                      cfg.get("rope_theta", 1e6))),
            dtype=dt)
    if arch == "dots3_note":
        from deepspeed_tpu.inference.v2.model_implementations. \
            ragged_dots3_note import Dots3NoteConfig

        if cfg.get("rope_scaling") is not None or cfg.get("attention_bias") \
                or cfg.get("tie_word_embeddings"):
            raise HFLoadError(
                "dots3_note: rope_scaling, attention_bias and a tied head "
                "are not implemented (dots3-note-prev sets none of them)")
        fields = {f.name for f in dataclasses.fields(Dots3NoteConfig)} \
            - {"dtype"}
        # (the swa_* keys, layer_types, sliding_window_size, both gate
        # types and apply_mla_qkv_lora_rescale are fields under their
        # published names; a gate type other than headwise and group-limited
        # routing are refused by the config, by name; the towers and
        # multi-token prediction have no key here and their tensors are
        # refused by load_hf_checkpoint)
        return arch, Dots3NoteConfig(
            **{k: v for k, v in cfg.items() if k in fields}, dtype=dt)
    if arch == "longcat_flash":
        from deepspeed_tpu.inference.v2.model_implementations. \
            ragged_longcat_flash import LongcatFlashConfig

        if cfg.get("rope_scaling") is not None or cfg.get("attention_bias") \
                or cfg.get("tie_word_embeddings"):
            raise HFLoadError(
                "longcat_flash: rope_scaling, attention_bias and a tied "
                "head are not implemented (LongCat-Flash sets none of "
                "them)")
        fields = {f.name for f in dataclasses.fields(LongcatFlashConfig)} \
            - {"dtype"}
        # (zero_expert_type other than identity: the config refuses it by
        # name; the multi-token-prediction head has no key here and its
        # tensors, if a checkpoint carries them, are not mapped)
        return arch, LongcatFlashConfig(
            **{k: v for k, v in cfg.items() if k in fields}, dtype=dt)
    if arch == "lfm2_moe":
        from deepspeed_tpu.inference.v2.model_implementations. \
            ragged_lfm2 import Lfm2Config

        rope = cfg.get("rope_parameters") or {}
        if rope.get("rope_type", "default") != "default" \
                or cfg.get("rope_scaling") is not None:
            raise HFLoadError(
                "lfm2_moe: a scaled rotary embedding is not implemented "
                "(LFM2-24B-A2B has rope_type default)")
        fields = {f.name for f in dataclasses.fields(Lfm2Config)} \
            - {"dtype", "rope_theta"}
        # (conv_bias, use_expert_bias false: the config refuses each by
        # name; tie_embedding is the family's key for the tied head)
        return arch, Lfm2Config(
            **{k: v for k, v in cfg.items() if k in fields},
            rope_theta=float(rope.get("rope_theta",
                                      cfg.get("rope_theta", 1e6))),
            dtype=dt)
    if arch == "afmoe":
        from deepspeed_tpu.inference.v2.model_implementations. \
            ragged_afmoe import AfmoeConfig

        fields = {f.name for f in dataclasses.fields(AfmoeConfig)} \
            - {"dtype", "held_experts", "expert_start"}
        # (n_group / topk_group > 1, rope_scaling, another score_func, a
        # tied head: the config refuses each by name)
        return arch, AfmoeConfig(
            **{k: v for k, v in cfg.items() if k in fields}, dtype=dt)
    if arch == "ouro":
        from deepspeed_tpu.inference.v2.model_implementations. \
            ragged_ouro import OuroConfig

        fields = {f.name for f in dataclasses.fields(OuroConfig)} - {"dtype"}
        # (early_exit_threshold < 1, rope_scaling, a sliding window, a tied
        # head: the config refuses each by name)
        return arch, OuroConfig(
            **{k: v for k, v in cfg.items() if k in fields}, dtype=dt)
    if arch == "jamba":
        from deepspeed_tpu.inference.v2.model_implementations. \
            ragged_jamba import JambaConfig

        if int(cfg.get("num_experts", 1)) > 1:
            raise HFLoadError(
                f"jamba: num_experts={cfg['num_experts']}: routed experts "
                f"beside state-space layers are not implemented (the dense "
                f"sibling, num_experts 1, is: AI21-Jamba2-3B)")
        fields = {f.name for f in dataclasses.fields(JambaConfig)} \
            - {"dtype"}
        # (mamba_proj_bias, a sliding window: the config refuses each by
        # name)
        return arch, JambaConfig(
            **{k: v for k, v in cfg.items() if k in fields}, dtype=dt)
    if arch == "granitemoehybrid":
        from deepspeed_tpu.inference.v2.model_implementations. \
            ragged_granite_moe_hybrid import GraniteMoeHybridConfig

        fields = {f.name for f in dataclasses.fields(GraniteMoeHybridConfig)
                  } - {"dtype", "held_experts", "expert_start"}
        # (more than one group, a rotary variant, a bias in the
        # projections, an untied head: the config refuses each by name)
        return arch, GraniteMoeHybridConfig(
            **{k: v for k, v in cfg.items() if k in fields}, dtype=dt)
    if arch == "gpt2":
        from deepspeed_tpu.models.gpt2 import GPT2Config

        return arch, GPT2Config(
            vocab_size=cfg["vocab_size"],
            hidden_size=cfg["n_embd"],
            num_hidden_layers=cfg["n_layer"],
            num_attention_heads=cfg["n_head"],
            max_position_embeddings=cfg["n_positions"],
            layer_norm_epsilon=cfg.get("layer_norm_epsilon", 1e-5),
            # HF n_inner (null in most checkpoints -> 4*n_embd), same
            # shape-error fix as the gptj branch below
            intermediate_size=cfg.get("n_inner") or 4 * cfg["n_embd"],
            dtype=dt)
    if arch == "opt":
        from deepspeed_tpu.models.opt import OPTConfig

        return arch, OPTConfig(
            vocab_size=cfg["vocab_size"],
            hidden_size=cfg["hidden_size"],
            ffn_dim=cfg["ffn_dim"],
            num_hidden_layers=cfg["num_hidden_layers"],
            num_attention_heads=cfg["num_attention_heads"],
            max_position_embeddings=cfg["max_position_embeddings"],
            do_layer_norm_before=cfg.get("do_layer_norm_before", True),
            dtype=dt)
    if arch == "falcon":
        from deepspeed_tpu.models.falcon import FalconConfig

        if not cfg.get("parallel_attn", True):
            raise HFLoadError(
                "only parallel-attention Falcon variants are supported "
                "(as in the reference, falcon/model.py:132)")
        if cfg.get("alibi", False):
            raise HFLoadError(
                "alibi Falcon variants are not supported — the models "
                "here apply rotary embeddings")
        if cfg.get("new_decoder_architecture", False):
            raise HFLoadError(
                "Falcon new_decoder_architecture (dual ln_attn/ln_mlp "
                "norms, 40B/180B) is not supported yet; the 7B-style "
                "parallel-attention layout is")
        kv = 1 if cfg.get("multi_query", True) else \
            cfg["num_attention_heads"]
        return arch, FalconConfig(
            vocab_size=cfg["vocab_size"],
            hidden_size=cfg["hidden_size"],
            num_hidden_layers=cfg["num_hidden_layers"],
            num_attention_heads=cfg["num_attention_heads"],
            num_kv_heads=kv,
            layer_norm_epsilon=cfg.get("layer_norm_epsilon", 1e-5),
            rope_theta=cfg.get("rope_theta", 10000.0),
            bias=cfg.get("bias", False),
            dtype=dt)
    if arch == "bloom":
        from deepspeed_tpu.models.bloom import BloomConfig

        return arch, BloomConfig(
            vocab_size=cfg["vocab_size"],
            hidden_size=cfg.get("hidden_size", cfg.get("n_embed")),
            num_hidden_layers=cfg.get("n_layer",
                                      cfg.get("num_hidden_layers")),
            num_attention_heads=cfg.get("n_head",
                                        cfg.get("num_attention_heads")),
            layer_norm_epsilon=cfg.get("layer_norm_epsilon", 1e-5),
            apply_residual_connection_post_layernorm=cfg.get(
                "apply_residual_connection_post_layernorm", False),
            dtype=dt)
    if arch == "gptj":
        from deepspeed_tpu.models.gptj import GPTJConfig

        return arch, GPTJConfig(
            vocab_size=cfg["vocab_size"],
            hidden_size=cfg["n_embd"],
            num_hidden_layers=cfg["n_layer"],
            num_attention_heads=cfg["n_head"],
            rotary_dim=cfg.get("rotary_dim") or cfg["n_embd"] //
            cfg["n_head"],
            max_position_embeddings=cfg["n_positions"],
            layer_norm_epsilon=cfg.get("layer_norm_epsilon", 1e-5),
            # HF n_inner (null in most checkpoints -> 4*n_embd); without
            # this, non-default-n_inner checkpoints shape-error on fc_in
            intermediate_size=cfg.get("n_inner") or 4 * cfg["n_embd"],
            dtype=dt)
    if arch in ("gpt_neox", "gptneox"):
        from deepspeed_tpu.models.gptneox import GPTNeoXConfig

        return arch, GPTNeoXConfig(
            vocab_size=cfg["vocab_size"],
            hidden_size=cfg["hidden_size"],
            intermediate_size=cfg["intermediate_size"],
            num_hidden_layers=cfg["num_hidden_layers"],
            num_attention_heads=cfg["num_attention_heads"],
            rotary_pct=cfg.get("rotary_pct", 0.25),
            rope_theta=cfg.get("rotary_emb_base",
                               cfg.get("rope_theta", 10000.0)),
            max_position_embeddings=cfg["max_position_embeddings"],
            layer_norm_eps=cfg.get("layer_norm_eps", 1e-5),
            use_parallel_residual=cfg.get("use_parallel_residual", True),
            dtype=dt)
    if arch == "bert":
        from deepspeed_tpu.models.bert import BertConfig

        return arch, BertConfig(
            vocab_size=cfg["vocab_size"],
            hidden_size=cfg["hidden_size"],
            intermediate_size=cfg["intermediate_size"],
            num_hidden_layers=cfg["num_hidden_layers"],
            num_attention_heads=cfg["num_attention_heads"],
            max_position_embeddings=cfg["max_position_embeddings"],
            type_vocab_size=cfg.get("type_vocab_size", 2),
            layer_norm_eps=cfg.get("layer_norm_eps", 1e-12),
            dtype=dt)
    raise HFLoadError(f"unsupported model_type {arch!r} in {model_path}")


# --------------------------------------------------------------------- #
# Loader
# --------------------------------------------------------------------- #
def _spec_for(path: Tuple[str, ...], rules) -> Any:
    from jax.sharding import PartitionSpec as P

    name = "/".join(path)
    for pat, spec in rules:
        if re.search(pat, name):
            return spec
    return P()


def load_hf_checkpoint(model_path: str, architecture: Optional[str] = None,
                       dtype: Any = None, mesh: Any = None,
                       rules: Any = None, strict: bool = True,
                       to_device: bool = True):
    """Load a HF checkpoint directory into a deepspeed_tpu flax param tree.

    ``architecture`` defaults to config.json's ``model_type``.  ``dtype``
    casts every tensor (e.g. ``jnp.bfloat16`` for serving, ``jnp.float32``
    for training masters); None keeps the stored dtype.  With ``mesh``
    each tensor lands pre-sharded by its policy PartitionSpec (``rules``
    overrides :func:`policy_for`'s registry lookup).  ``strict`` raises on
    unmapped tensor names instead of skipping them.  ``to_device=False``
    keeps every tensor on the HOST (numpy) — for consumers that stream
    leaves through their own placement/quantization (at most one tensor
    transits the device at a time, never the full tree).
    """
    import jax
    import jax.numpy as jnp

    if not to_device and mesh is not None:
        raise ValueError(
            "to_device=False keeps tensors on the host; it cannot be "
            "combined with mesh= (which device_puts every tensor)")
    try:
        file_cfg = hf_config(model_path)
    except FileNotFoundError:
        # config.json is optional when architecture= is given explicitly
        file_cfg = {}
    if architecture is None:
        architecture = file_cfg.get("model_type", "")
    arch = architecture.lower()
    if arch not in _ARCH_RULES:
        raise HFLoadError(
            f"no HF name map for architecture {arch!r} "
            f"(have: {sorted(_ARCH_RULES)})")
    rule_list = [(re.compile(p), fn) for p, fn in _ARCH_RULES[arch]()]
    if mesh is not None and rules is None:
        from deepspeed_tpu.module_inject.replace_policy import policy_for

        rules = policy_for(arch)
        if rules is None:
            raise HFLoadError(f"no TP policy registered for {arch!r}")

    tree: Dict[str, Any] = {}
    stacks: Dict[Tuple[str, ...], Dict[int, Any]] = {}
    # Flush a leaf's expert stack the moment its last expert arrives, so at
    # most one layer's expert set is host-resident (Mixtral expert weights
    # are ~95% of parameters; buffering them all would hold the whole model
    # on the host, defeating the streaming design).
    n_experts = file_cfg.get("num_local_experts") or \
        file_cfg.get("num_experts") or file_cfg.get("n_routed_experts")

    def flush_stack(path):
        parts = stacks.pop(path)
        n = max(parts) + 1
        if set(parts) != set(range(n)):
            raise HFLoadError(
                f"missing expert shards for {'/'.join(path)}: "
                f"have {sorted(parts)}")
        place(path, np.stack([parts[i] for i in range(n)]))

    def place(path, arr):
        if not to_device and mesh is None:
            arr = np.asarray(jax.device_get(arr)
                             if isinstance(arr, jax.Array) else arr)
            if dtype is not None:
                arr = arr.astype(np.dtype(jnp.dtype(dtype)))
        elif dtype is not None:
            arr = jnp.asarray(arr, dtype=dtype)
        if mesh is not None:
            from jax.sharding import NamedSharding

            arr = jax.device_put(
                arr, NamedSharding(mesh, _spec_for(path, rules)))
        elif to_device:
            arr = jnp.asarray(arr)
        node = tree
        for k in path[:-1]:
            node = node.setdefault(k, {})
        node[path[-1]] = arr

    unmapped = []
    for name, tensor in iter_checkpoint_tensors(model_path):
        for pat, fn in rule_list:
            m = pat.match(name)
            if m is None:
                continue
            path, tf = fn(m)
            if path is None:            # deliberately skipped tensor
                break
            if isinstance(path, list):  # one tensor, several leaves
                for leaf, part in path:
                    place(leaf, part(tensor, file_cfg))
                break
            if isinstance(tf, tuple) and tf[0] == "stack":
                stacks.setdefault(path, {})[tf[1]] = np.asarray(tensor).T
                if n_experts and len(stacks[path]) == n_experts:
                    flush_stack(path)
            else:
                arr = tensor.T if tf == "t" else \
                    tf(tensor, file_cfg) if callable(tf) else tensor
                place(path, arr)
            break
        else:
            unmapped.append(name)
    if unmapped and strict:
        raise HFLoadError(
            f"unmapped tensors for {arch}: {unmapped[:8]}"
            + (f" (+{len(unmapped) - 8} more)" if len(unmapped) > 8 else ""))
    for path in list(stacks):
        flush_stack(path)
    if arch == "glm_moe_dsa" and file_cfg.get("num_nextn_predict_layers"):
        # the multi-token-prediction layers follow the model's own
        for i in range(int(file_cfg["num_nextn_predict_layers"])):
            tree.pop(f"layers_{int(file_cfg['num_hidden_layers']) + i}",
                     None)
    if arch == "dots3_note" and "num_hidden_layers" in file_cfg:
        extra = sorted(k for k in tree if k.startswith("layers_")
                       and int(k[7:]) >= int(file_cfg["num_hidden_layers"]))
        if extra:
            raise HFLoadError(
                f"dots3_note: the checkpoint holds {extra} past "
                f"num_hidden_layers={file_cfg['num_hidden_layers']} "
                f"(multi-token-prediction layers), which RaggedDots3Note "
                f"does not serve")
    return tree


def model_from_hf(model_path: str, dtype: Any = None):
    """Build the matching deepspeed_tpu flax module for a HF checkpoint
    directory.  Returns ``(architecture, config, module)`` — pair with
    :func:`load_hf_checkpoint` for the params."""
    arch, cfg = config_from_hf(model_path, dtype)
    if arch in ("llama", "mistral", "internlm"):
        from deepspeed_tpu.models.llama import LlamaForCausalLM

        return arch, cfg, LlamaForCausalLM(cfg)
    if arch in ("mixtral", "olmoe"):
        from deepspeed_tpu.models.mixtral import MixtralForCausalLM

        return arch, cfg, MixtralForCausalLM(cfg)
    if arch == "gpt2":
        from deepspeed_tpu.models.gpt2 import GPT2LMHeadModel

        return arch, cfg, GPT2LMHeadModel(cfg)
    if arch == "opt":
        from deepspeed_tpu.models.opt import OPTForCausalLM

        return arch, cfg, OPTForCausalLM(cfg)
    if arch == "falcon":
        from deepspeed_tpu.models.falcon import FalconForCausalLM

        return arch, cfg, FalconForCausalLM(cfg)
    if arch == "bloom":
        from deepspeed_tpu.models.bloom import BloomForCausalLM

        return arch, cfg, BloomForCausalLM(cfg)
    if arch == "gptj":
        from deepspeed_tpu.models.gptj import GPTJForCausalLM

        return arch, cfg, GPTJForCausalLM(cfg)
    if arch in ("gpt_neox", "gptneox"):
        from deepspeed_tpu.models.gptneox import GPTNeoXForCausalLM

        return arch, cfg, GPTNeoXForCausalLM(cfg)
    if arch == "bert":
        from deepspeed_tpu.models.bert import BertModel

        return arch, cfg, BertModel(cfg)
    raise HFLoadError(f"no model class for architecture {arch!r}")
