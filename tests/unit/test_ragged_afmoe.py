"""RaggedAfmoe (``model_type: afmoe``, the Trinity family) against the
benchmark's plain float32 reference (``benchmark/reference/afmoe.py``: no
cache, the band mask written as an inequality), at tiny sizes on the CPU.

The window is 40 tokens over blocks of 16, so a prompt of a hundred tokens
fed in chunks releases window blocks inside every test that serves one.
(a) the float32 engine through ``put`` in chunks + ``decode_step``, packed
back to back and in the two-segment layout, and a bf16 engine on rounded
weights; every fault of the chip's fault table fails the tolerance.  (b)
sequences interleaved through the scheduler; a block one sequence released
and another reused changes no logit.  (c) the router selects by score +
bias and weighs by score; the eight expert shares plus the shared expert
once add up to the uncut layer.  (d) what is not computed is refused by
name.  (e) device scopes of both kinds of layer; the one-token rows of a
window layer take the decode walk with the window table.  (f) the loader on
a checkpoint under the published tensor names.
"""

from __future__ import annotations

import json
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for _path in (_ROOT, os.path.join(_ROOT, "tools"),
              os.path.dirname(os.path.abspath(__file__))):
    sys.path.insert(0, _path)

from benchmark.families import afmoe as family                # noqa: E402
from benchmark.reference import afmoe as reference            # noqa: E402
from benchmark.tools.calls import pr39_faults                 # noqa: E402
from deepspeed_tpu.inference.v2.kernels import blocked_flash  # noqa: E402
from deepspeed_tpu.inference.v2.model_implementations import (  # noqa: E402
    AfmoeConfig, RaggedAfmoe, ragged_afmoe)
from deepspeed_tpu.inference.v2.modules import attention      # noqa: E402
from deepspeed_tpu.inference.v2.modules.moe import dropless_moe  # noqa: E402
from test_kv_groups import BS, HF, WINDOW, engine, ids, params  # noqa: E402

# float32 engine against the float32 reference, largest |difference| over
# the largest |reference logit|: the same mathematics in another order
F32_TOL = 2e-5
# bf16 engine against the float32 reference on the same bf16-rounded weights
BF16_TOL = 0.05


def _serve(eng, tokens, n_prompt, uid=7, chunks=None):
    """Logits after the prompt (fed whole, or in ``chunks``) and after each
    further token through ``decode_step``."""
    if chunks:
        at = 0
        for n in chunks:
            out = eng.put([uid], [tokens[at:at + n].tolist()])
            at += n
        assert at == n_prompt
    else:
        out = eng.put([uid], [tokens[:n_prompt].tolist()])
    got = [np.asarray(out[uid], np.float32)]
    for t in tokens[n_prompt:-1]:
        got.append(np.asarray(eng.decode_step([uid], [int(t)]),
                              np.float32)[0])
    eng.flush([uid])
    return np.stack(got)


def _want(p, tokens, n_prompt, hf=HF):
    return reference.logits_at(
        family.reference_params(p), tokens[:-1], hf,
        rows=list(range(n_prompt - 1, len(tokens) - 1)))


def _gap(got, want) -> float:
    assert got.shape == want.shape and np.isfinite(got).all()
    return float(np.max(np.abs(got - want)) / np.max(np.abs(want)))


# ------------------------------------------------------------------ #
# (a) one sequence against the reference
# ------------------------------------------------------------------ #
@pytest.mark.parametrize("budget, tile, chunks", [
    (24, None, None), (64, 16, None), (64, 16, [64, 33, 1, 42]),
    (32, 16, [7, 90, 43])], ids=[
        "packed_back_to_back", "two_segments", "a_chunk_of_one_row",
        "a_chunk_past_the_window"])
def test_f32_engine_matches_reference(budget, tile, chunks):
    p, tokens = params(), ids(140 + 10)
    eng = engine(p, budget=budget, tile=tile)
    sm = eng.state_manager
    assert _gap(_serve(eng, tokens, 140, chunks=chunks),
                _want(p, tokens, 140)) <= F32_TOL
    # blocks were released inside the test, and all came back
    assert sm.win_released >= (140 - WINDOW) // BS
    assert sm.win_allocator.free_blocks == sm.win_allocator.num_blocks - 1
    assert sm.allocator.free_blocks == sm.allocator.num_blocks - 1


def test_bf16_engine_is_the_same_model():
    p, tokens = params(), ids(100 + 6)
    rounded = jax.tree.map(
        lambda a: a.astype(jnp.bfloat16).astype(jnp.float32), p)
    got = _serve(engine(jax.tree.map(lambda a: a.astype(jnp.bfloat16), p),
                        compute=jnp.bfloat16), tokens, 100)
    assert _gap(got, _want(rounded, tokens, 100)) <= BF16_TOL


@pytest.mark.parametrize("fault", pr39_faults.FAULTS)
def test_a_seeded_fault_fails_the_tolerance(fault):
    """The negative cases the chip's check is held to
    (``benchmark/tools/calls/pr39_faults.py`` says what each is)."""
    p, tokens = params(), ids(140 + 6)
    want = _want(p, tokens, 140)
    with pr39_faults.fault(fault, window=WINDOW) as fix:
        got = _serve(fix(engine(p, budget=64, tile=16)), tokens, 140)
    assert _gap(got, want) > 100 * F32_TOL


# ------------------------------------------------------------------ #
# (b) several sequences through the scheduler
# ------------------------------------------------------------------ #
PROMPT_LENS, NEW = (150, 40, 90, 7, 33, 65), (4, 9, 5, 12, 6, 5)


@pytest.mark.parametrize("seqs", [4, 2])
def test_interleaved_logits_match_each_reference(seqs):
    """Six requests over four slots, then over two: chunks of one beside
    decodes of another, joins and leaves, and (two slots: a window pool of
    12 blocks for 20 blocks of band in all) window blocks that one request
    released written by the next."""
    from interleaved_logits import serve_and_compare

    p = params()
    prompts = [ids(n, seed=10 + i).tolist()
               for i, n in enumerate(PROMPT_LENS)]
    eng = engine(p, seqs=seqs)
    sm = eng.state_manager
    handed = []
    real = sm.win_allocator.allocate
    sm.win_allocator.allocate = lambda n: handed.extend(real(n)) or \
        handed[-n:]
    out = serve_and_compare(eng, reference, family.reference_params(p), HF,
                            prompts, NEW)
    assert len(out["gaps"]) == 6 and max(out["gaps"]) <= F32_TOL, out
    assert len(handed) > len(set(handed)) or seqs == 4
    assert sm.win_allocator.free_blocks == sm.win_allocator.num_blocks - 1


def test_a_released_then_reused_block_changes_no_logit():
    """A's released window blocks go to B while A is live; A's later logits
    are the reference's all the same."""
    p = params()
    a, b = ids(100 + 12), ids(100, seed=5)
    eng = engine(p, seqs=2)
    sm = eng.state_manager
    first = eng.put([1], [a[:100].tolist()])[1]
    seq_a = sm.get_sequence(1)
    mine = set(seq_a.win_blocks)
    free_before = set(sm.win_allocator._free)
    eng.put([2], [b.tolist()])
    seq_b = sm.get_sequence(2)
    # B went through every free block A had given back, and none of A's own
    assert sm.win_released >= 6
    assert not mine & set(seq_b.win_blocks) and mine == set(seq_a.win_blocks)
    assert free_before & set(seq_b.win_blocks)
    got = [np.asarray(first, np.float32)]
    for t in a[100:-1]:
        got.append(np.asarray(eng.decode_step([1, 2], [int(t), 3]),
                              np.float32)[0])
    assert _gap(np.stack(got), _want(p, a, 100)) <= F32_TOL


# ------------------------------------------------------------------ #
# (c) the router and the share
# ------------------------------------------------------------------ #
def _moe_params(p, layer=1):
    return family._seeded_bias(p, "e_score_correction_bias")[
        f"layers_{layer}"]["mlp"]


def test_router_bias_selects_and_does_not_weigh():
    from deepspeed_tpu.inference.v2.modules.moe import moe_router

    mlp = _moe_params(params())
    x = jax.random.normal(jax.random.key(1), (50, 64), jnp.float32)
    wg, bias = mlp["gate"]["wg"]["kernel"], \
        mlp["gate"]["e_score_correction_bias"]
    topi, w = moe_router(x, wg, 2, True, bias=bias, routed_scale=2.448)
    s = np.asarray(jax.nn.sigmoid(x @ wg))
    b = np.asarray(bias, np.float32)
    want_i = np.argsort(-(s + b), axis=-1)[:, :2]
    assert (np.sort(np.asarray(topi), -1) == np.sort(want_i, -1)).all()
    # the bias moved some selections, and weighs nothing
    assert (np.sort(want_i, -1) != np.sort(
        np.argsort(-s, axis=-1)[:, :2], -1)).any()
    chosen = np.take_along_axis(s, np.asarray(topi), -1)
    np.testing.assert_allclose(
        np.asarray(w), 2.448 * chosen / chosen.sum(-1, keepdims=True),
        rtol=1e-5)
    ri, rw = reference.route(x, wg, bias, 2, True, 2.448)
    assert (np.asarray(ri) == np.asarray(topi)).all()
    np.testing.assert_allclose(np.asarray(rw), np.asarray(w), rtol=1e-5)


def test_the_shares_and_the_shared_expert_once_add_up_to_the_uncut_layer():
    """Eight router outputs, two experts a share: the four shares' routed
    parts plus the shared expert once are the layer with every expert
    held."""
    hf = {**HF, "num_experts": 8}
    mlp = _moe_params(params(hf))
    x = jax.random.normal(jax.random.key(2), (40, 64), jnp.float32)
    whole = dropless_moe(x, mlp, 2, jnp.float32, renormalize=True,
                         routed_scale=2.448)
    routed = {k: v for k, v in mlp.items() if k != "shared_expert"}
    shared = whole - dropless_moe(x, routed, 2, jnp.float32,
                                  renormalize=True, routed_scale=2.448)
    assert float(jnp.abs(shared).max()) > 0.01
    parts = []
    for start in range(0, 8, 2):
        share = {**routed, "experts": {
            k: v[start:start + 2] for k, v in mlp["experts"].items()}}
        parts.append(dropless_moe(x, share, 2, jnp.float32,
                                  renormalize=True, routed_scale=2.448,
                                  expert_start=start))
    np.testing.assert_allclose(np.asarray(sum(parts) + shared),
                               np.asarray(whole), atol=2e-5)
    # and the reference's share is the program's
    idx_ref = reference.routed(
        x, {"router": mlp["gate"]["wg"]["kernel"],
            "bias": mlp["gate"]["e_score_correction_bias"],
            **{k: v[2:4] for k, v in mlp["experts"].items()}},
        top_k=2, norm_topk=True, scale=2.448, expert_start=2)
    np.testing.assert_allclose(np.asarray(idx_ref), np.asarray(parts[1]),
                               atol=2e-5)


# ------------------------------------------------------------------ #
# (d) what is not computed is refused by name
# ------------------------------------------------------------------ #
@pytest.mark.parametrize("key, value, match", [
    ("n_group", 2, "n_group=2"), ("topk_group", 2, "topk_group=2"),
    ("rope_scaling", {"type": "yarn"}, "rope_scaling"),
    ("score_func", "softmax", "score_func='softmax'"),
    ("tie_word_embeddings", True, "tie_word_embeddings")])
def test_what_is_not_computed_is_refused_by_name(key, value, match):
    with pytest.raises(NotImplementedError, match=match):
        family.program_config({**HF, key: value})
    if key != "tie_word_embeddings":
        with pytest.raises(ValueError, match="reference/afmoe.py"):
            reference._check({**HF, key: value})


def test_layer_types_default_to_the_published_pattern():
    cfg = AfmoeConfig(num_hidden_layers=8)
    assert [cfg.is_window(i) for i in range(8)] == [
        True, True, True, False, True, True, True, False]
    groups = RaggedAfmoe(cfg, 16).kv_groups
    # the global layers (3, 7) are the ones not named
    assert groups == {"window": {"layers": [0, 1, 2, 4, 5, 6],
                                 "window": 4096}}
    with pytest.raises(ValueError, match="layer_types"):
        AfmoeConfig(num_hidden_layers=3, layer_types=["full_attention"] * 2)


def test_int8_pools_are_refused():
    with pytest.raises(ValueError, match="int8"):
        engine(params(), dtype="int8")


# ------------------------------------------------------------------ #
# (e) scopes and routes
# ------------------------------------------------------------------ #
def test_device_scopes_of_both_kinds_of_layer():
    eng = engine(params(), budget=64, tile=16)
    eng.put([1], [ids(20).tolist()])
    eng.put([1, 2], [[5], ids(30, seed=2).tolist()])
    eng.decode_step([1, 2], [3, 4])
    assert len(eng.step_keys) == 2      # one two-segment program, decode_step
    for key in eng.step_keys:
        text = eng.lower_step(key).as_text(debug_info=True)
        for scope in ("layers_0/attn/qkv", "layers_0/attn/rope_insert",
                      "layers_0/attn/swa_read", "layers_2/attn/full_read",
                      "layers_2/attn/rope_insert", "layers_4/attn/swa_read",
                      "layers_1/attn/gate", "layers_2/attn/gate",
                      "layers_3/attn/out_proj", "layers_0/mlp",
                      "layers_1/moe/router", "layers_1/moe/dispatch",
                      "layers_1/moe/experts", "layers_1/moe/combine",
                      "layers_1/moe/shared", "lm_head"):
            assert scope in text, (key, scope)
        assert "layers_2/attn/swa_read" not in text
        assert "layers_0/attn/full_read" not in text
        assert "layers_0/moe" not in text and "layers_1/mlp" not in text


def test_one_token_rows_of_both_kinds_take_the_walk(monkeypatch):
    """With the chip's route (interpret mode), at heads of 128: every
    one-token read is ``_decode_kernel`` (the window layers' with their own
    table and the band as its ``lo``), the chunks the tiled kernel, and the
    logits are the reference's."""
    hf = {**HF, "head_dim": 128, "num_attention_heads": 2,
          "num_key_value_heads": 1, "num_hidden_layers": 2,
          "layer_types": ["sliding_attention", "full_attention"],
          "hidden_size": 32, "intermediate_size": 64,
          "moe_intermediate_size": 32}
    monkeypatch.setattr(attention, "on_tpu", lambda: True)
    import deepspeed_tpu.inference.v2.kernels as kernels

    calls = {"paged_decode_attention": [], "paged_prefill_attention": []}
    for name, seen in calls.items():
        real = getattr(blocked_flash, name)
        monkeypatch.setattr(kernels, name, lambda *a, _real=real, _seen=seen,
                            **k: (_seen.append(k.get("window")),
                                  _real(*a, **k))[1])
    p, tokens = params(hf), ids(100 + 4)
    eng = engine(p, hf=hf, budget=64, tile=16)
    got = _serve(eng, tokens, 100, chunks=[64, 35, 1])
    assert _gap(got, _want(p, tokens, 100, hf)) <= F32_TOL
    # three programs (two-segment with and without a tile segment, the
    # decode step), each traced once: a walk a layer in each, the tiled
    # kernel in the one with chunks
    assert sorted(calls["paged_decode_attention"], key=str) == \
        [WINDOW] * 3 + [None] * 3
    assert sorted(calls["paged_prefill_attention"], key=str) == \
        [WINDOW, None]


# ------------------------------------------------------------------ #
# (f) a checkpoint under the published tensor names
# ------------------------------------------------------------------ #
def test_loader_on_a_synthetic_afmoe_state_dict(tmp_path):
    """Tensors named and laid out as the published checkpoint has them
    ([out, in] matrices, one ``experts.<e>`` module an expert,
    ``router.gate``, the ``expert_bias`` buffer, ``shared_experts``, the
    attention's ``gate_proj`` beside the MLP's, four norms a layer): the
    loaded tree is the model's, and the engine serves the reference's
    logits."""
    from safetensors.numpy import save_file

    from deepspeed_tpu.checkpoint.hf_loader import (config_from_hf,
                                                    load_hf_checkpoint)
    from deepspeed_tpu.inference.v2 import InferenceEngineV2

    hf = {**HF, "num_experts": 8}
    hf.pop("router_experts"), hf.pop("expert_start")
    p = params(hf, seed=4)
    tensors = {}

    def put(name, a):
        tensors[name] = np.ascontiguousarray(np.asarray(a, np.float32))

    put("model.embed_tokens.weight", p["embed_tokens"]["embedding"])
    put("model.norm.weight", p["norm"]["scale"])
    put("lm_head.weight", p["lm_head"]["kernel"].T)
    for i in range(hf["num_hidden_layers"]):
        lp, pre = p[f"layers_{i}"], f"model.layers.{i}."
        for norm in ("input_layernorm", "post_attention_layernorm",
                     "pre_mlp_layernorm", "post_mlp_layernorm"):
            put(pre + norm + ".weight", lp[norm]["scale"])
        att = lp["self_attn"]
        for proj in ("q_proj", "k_proj", "v_proj", "o_proj", "gate_proj"):
            put(f"{pre}self_attn.{proj}.weight", att[proj]["kernel"].T)
        put(pre + "self_attn.q_norm.weight", att["q_norm"]["scale"])
        put(pre + "self_attn.k_norm.weight", att["k_norm"]["scale"])
        mlp = lp["mlp"]
        if "gate" not in mlp:
            for proj in ("gate_proj", "up_proj", "down_proj"):
                put(f"{pre}mlp.{proj}.weight", mlp[proj]["kernel"].T)
            continue
        put(pre + "mlp.router.gate.weight", mlp["gate"]["wg"]["kernel"].T)
        put(pre + "mlp.expert_bias", mlp["gate"]["e_score_correction_bias"])
        for proj in ("gate_proj", "up_proj", "down_proj"):
            put(f"{pre}mlp.shared_experts.{proj}.weight",
                mlp["shared_expert"][proj]["kernel"].T)
        for e in range(8):
            for proj, leaf in (("gate_proj", "w_gate"), ("up_proj", "w_up"),
                               ("down_proj", "w_down")):
                put(f"{pre}mlp.experts.{e}.{proj}.weight",
                    mlp["experts"][leaf][e].T)
    save_file(tensors, str(tmp_path / "model.safetensors"))
    published = {k: v for k, v in hf.items()}
    published["architectures"] = ["AfmoeForCausalLM"]
    (tmp_path / "config.json").write_text(json.dumps(published))

    arch, cfg = config_from_hf(str(tmp_path), jnp.float32)
    assert arch == "afmoe" and cfg.layer_types == hf["layer_types"]
    assert cfg.num_experts == 8 and cfg.held_experts is None
    loaded = load_hf_checkpoint(str(tmp_path), dtype=jnp.float32)
    flat_w = dict(jax.tree_util.tree_flatten_with_path(p)[0])
    flat_g = dict(jax.tree_util.tree_flatten_with_path(loaded)[0])
    assert set(flat_w) == set(flat_g)
    for path, a in flat_w.items():
        np.testing.assert_array_equal(np.asarray(a), np.asarray(flat_g[path]),
                                      err_msg=str(path))
    eng = InferenceEngineV2.from_hf(
        str(tmp_path), dtype=jnp.float32,
        config=engine(p, hf=hf).config)
    tokens = ids(60 + 5)
    got = _serve(eng, tokens, 60)
    ref = family.reference_params(loaded)
    # the loader applies no seeded-bias mapping: the reference reads the
    # bias as the checkpoint has it
    for layer, lp in zip(ref["layers"], range(hf["num_hidden_layers"])):
        if "bias" in layer:
            layer["bias"] = loaded[f"layers_{lp}"]["mlp"]["gate"][
                "e_score_correction_bias"]
    want = reference.logits_at(ref, tokens[:-1], hf,
                               rows=list(range(59, 64)))
    assert _gap(got, want) <= F32_TOL
