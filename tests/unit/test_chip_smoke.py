"""chip_smoke.py's CPU dry run, and the bring-up rules it rests on: no
fallback that hides the device, one process per chip, a compile cache that
can be placed from outside.

The chip run itself (Mistral-7B widths, Mosaic-compiled kernels) only
happens through the chip tool; here the same phases run at ``mistral_tiny``
size on the 8-device CPU mesh with the chip check waived explicitly — which
the command line cannot do.
"""

import dataclasses
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, _REPO)

import chip_smoke  # noqa: E402


def _tiny_sizes():
    from deepspeed_tpu.models.mistral import mistral_tiny

    return chip_smoke.SmokeSizes(
        model_config=mistral_tiny(dtype=jnp.bfloat16,
                                  max_position_embeddings=256),
        train_layers=2, train_seq=64, train_steps=5,
        serve_layers=2, block_size=8, token_budget=128, max_seqs=8,
        prompt_lens=(5, 9, 17, 30, 44, 61, 75, 90),
        new_tokens=(8, 6, 4, 8, 6, 4, 8, 6),
        check_prompt_len=24)


def test_smoke_phases_tiny_on_cpu_mesh(monkeypatch):
    """Train and serve phases end to end on the virtual mesh: ZeRO-3 x TP
    training with falling loss and no recompilation, tensor-parallel
    serving of mixed-length requests, shards on every device."""
    placed = []
    monkeypatch.setattr("deepspeed_tpu.utils.compile_cache."
                        "enable_compile_cache",
                        lambda: placed.append(1) or "(not placed in tests)")
    out = chip_smoke.run(_tiny_sizes(), require_chip=False,
                         phases=("train", "serve"))
    assert out["ok"] and placed == [1]
    assert out["device"] == {"platform": "cpu", "kind": "cpu", "count": 8}

    train = out["train"]
    assert train["mesh"] == {"data": 4, "model": 2}
    assert train["zero_stage"] == 3 and train["steps"] == 6
    assert train["losses"][-1] < train["losses"][0]
    assert train["loss_route_diff"] <= chip_smoke.TRAIN_LOSS_TOL
    held = train["state_shards"]["per_device_bytes"]
    assert len(held) == 8 and max(held) <= 2 * min(held) and min(held) > 0

    serve = out["serve"]
    assert serve["tensor_parallel"] == 2          # 2 KV heads divide by 2
    assert serve["requests"] == 16
    assert serve["logit_err_vs_xla"] <= chip_smoke.SERVE_LOGIT_TOL
    assert "decode_step" in serve["attention_route"]
    # a budget of one whole tile: every put program, mixed ticks included,
    # is the two-segment one of 8 single-token rows and that tile
    assert {k for k in serve["attention_route"] if k != "decode_step"} == \
        {"prefill_T136_tiled"}
    assert len(serve["ttft_s"]) == 8 and len(serve["tpot_s"]) == 8
    for tree in serve["state_shards"].values():
        assert len(tree["per_device_bytes"]) == 2
        assert min(tree["per_device_bytes"]) > 0


def test_smoke_moe_phase_tiny_on_one_cpu_device():
    """The routed-expert phase's control flow at a tiny OLMoE-shaped size:
    the grouped GEMM (interpreted) against ``gmm_reference`` at two row
    counts, and the engine's grouped path against the dense composition,
    each program on the expert path it was built for."""
    from deepspeed_tpu.models.mixtral import MixtralConfig

    sizes = dataclasses.replace(
        _tiny_sizes(), token_budget=64, max_seqs=4,
        moe_config=MixtralConfig.olmoe_1b_7b(
            vocab_size=256, hidden_size=128, intermediate_size=128,
            num_hidden_layers=2, num_attention_heads=4,
            num_key_value_heads=4, max_position_embeddings=256,
            num_local_experts=8, num_experts_per_tok=2, dtype=jnp.bfloat16),
        moe_gmm_rows=(128, 256), moe_prompt_len=40, moe_new_tokens=3)
    out = chip_smoke.moe_phase(sizes, jax.devices()[:1], False,
                               chip_smoke.CompileClock())
    for name, rows in (("gmm_fwd_e64_decode", 128),
                       ("gmm_fwd_e64_prefill", 256)):
        assert out[name]["rows"] == rows
        assert out[name]["max_err"] <= chip_smoke.GMM_TOL
    assert out["router_f32_agreement"]["same_top_k"] >= \
        chip_smoke.ROUTER_AGREE_FLOOR
    serve = out["ragged_moe_serve"]
    assert serve["logit_err_vs_dense"] <= chip_smoke.MOE_LOGIT_TOL
    assert {n.split("/")[0] for n in serve["routes"]} == {"grouped", "dense"}
    assert any(n.endswith("decode_step") for n in serve["routes"])
    for name, r in serve["routes"].items():
        assert r["all_experts_einsum"] == name.startswith("dense/")
    # without a moe_config (a caller's own SmokeSizes) the phase says so
    assert "skipped" in chip_smoke.moe_phase(
        _tiny_sizes(), jax.devices()[:1], False, chip_smoke.CompileClock())


def test_smoke_gdn_phase_tiny_on_one_cpu_device(monkeypatch):
    """The linear-attention phase's control flow at a tiny Qwen3-Next-shaped
    size: two requests of different lengths interleaved through the
    scheduler (the longer one in two chunks), each against its own
    reference forward; every state slot given back.  The phase serves bf16
    against a float32 reference: at hidden 64 with top-3 of 8 experts a
    bf16 rounding flips routings and moves logits by tenths (measured 0.05
    and 0.19), which the published widths do not do; the float32 parity of
    the same interleaving is ``test_ragged_qwen3_next.py``'s (5e-7)."""
    monkeypatch.setattr(chip_smoke, "GDN_LOGIT_TOL", 0.5)
    hf = {"model_type": "qwen3_next", "vocab_size": 256, "hidden_size": 64,
          "num_hidden_layers": 4, "num_attention_heads": 4,
          "num_key_value_heads": 2, "head_dim": 16,
          "partial_rotary_factor": 0.25, "rope_theta": 10000,
          "rms_norm_eps": 1e-6, "max_position_embeddings": 512,
          "full_attention_interval": 4, "linear_num_key_heads": 2,
          "linear_num_value_heads": 4, "linear_key_head_dim": 16,
          "linear_value_head_dim": 16, "linear_conv_kernel_dim": 4,
          "num_experts": 4, "router_experts": 8, "expert_start": 2,
          "num_experts_per_tok": 3, "moe_intermediate_size": 32,
          "shared_expert_intermediate_size": 32, "norm_topk_prob": True,
          "rope_scaling": None}
    sizes = dataclasses.replace(
        _tiny_sizes(), token_budget=128, max_seqs=4, block_size=16,
        gdn_hf=hf, gdn_prompt_lens=(150, 40), gdn_new_tokens=(4, 7))
    out = chip_smoke.gdn_phase(sizes, jax.devices()[:1], False,
                               chip_smoke.CompileClock())
    assert max(out["logit_gaps"]) <= chip_smoke.GDN_LOGIT_TOL
    assert min(out["rows_compared"]) >= 4
    assert "decode_step" in out["kernels"]
    assert "skipped" in chip_smoke.gdn_phase(
        _tiny_sizes(), jax.devices()[:1], False, chip_smoke.CompileClock())


def test_smoke_mla_phase_tiny_on_one_cpu_device(monkeypatch):
    """The latent-attention phase's control flow at a tiny Moonlight-shaped
    size: two requests interleaved through the scheduler (the longer one in
    two chunks through the expanded composition, decodes through the
    absorbed one), each against its own reference forward.  bf16 against
    float32 at hidden 64 flips routings (as the gdn phase's test says); the
    float32 parity is ``test_ragged_deepseek_v3.py``'s."""
    monkeypatch.setattr(chip_smoke, "GDN_LOGIT_TOL", 0.5)
    hf = {"model_type": "deepseek_v3", "vocab_size": 256, "hidden_size": 64,
          "intermediate_size": 96, "moe_intermediate_size": 32,
          "num_hidden_layers": 3, "num_attention_heads": 4,
          "kv_lora_rank": 32, "q_lora_rank": None, "qk_nope_head_dim": 16,
          "qk_rope_head_dim": 8, "v_head_dim": 16, "n_routed_experts": 4,
          "router_experts": 8, "expert_start": 2, "n_shared_experts": 2,
          "num_experts_per_tok": 3, "first_k_dense_replace": 1,
          "moe_layer_freq": 1, "n_group": 1, "topk_group": 1,
          "norm_topk_prob": True, "routed_scaling_factor": 2.446,
          "scoring_func": "sigmoid", "topk_method": "noaux_tc",
          "rope_theta": 50000, "rms_norm_eps": 1e-5,
          "max_position_embeddings": 512}
    sizes = dataclasses.replace(
        _tiny_sizes(), token_budget=128, max_seqs=4, block_size=16,
        mla_hf=hf, gdn_prompt_lens=(150, 40), gdn_new_tokens=(4, 7))
    out = chip_smoke.mla_phase(sizes, jax.devices()[:1], False,
                               chip_smoke.CompileClock())
    assert max(out["logit_gaps"]) <= chip_smoke.GDN_LOGIT_TOL
    assert min(out["rows_compared"]) >= 4
    assert "decode_step" in out["kernels"]
    assert "skipped" in chip_smoke.mla_phase(
        _tiny_sizes(), jax.devices()[:1], False, chip_smoke.CompileClock())


def test_smoke_conv_phase_tiny_on_one_cpu_device(monkeypatch):
    """The short-convolution phase's control flow at a tiny LFM2-shaped
    size: two requests interleaved through the scheduler (the longer one in
    two chunks, its tails carried in a state slot), each against its own
    reference forward; every slot given back.  bf16 against float32 at
    hidden 64 flips routings (as the gdn phase's test says); the float32
    parity is ``test_ragged_lfm2.py``'s."""
    monkeypatch.setattr(chip_smoke, "GDN_LOGIT_TOL", 0.5)
    hf = {"model_type": "lfm2_moe", "vocab_size": 256, "hidden_size": 64,
          "intermediate_size": 96, "moe_intermediate_size": 32,
          "num_hidden_layers": 4,
          "layer_types": ["conv", "conv", "full_attention", "conv"],
          "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
          "conv_L_cache": 3, "conv_bias": False, "num_dense_layers": 2,
          "num_experts": 8, "num_experts_per_tok": 2, "norm_topk_prob": True,
          "use_expert_bias": True, "routed_scaling_factor": 1,
          "norm_eps": 1e-5, "max_position_embeddings": 512,
          "rope_parameters": {"rope_theta": 10000, "rope_type": "default"}}
    sizes = dataclasses.replace(
        _tiny_sizes(), token_budget=128, max_seqs=4, block_size=16,
        conv_hf=hf, gdn_prompt_lens=(150, 40), gdn_new_tokens=(4, 7))
    out = chip_smoke.conv_phase(sizes, jax.devices()[:1], False,
                                chip_smoke.CompileClock())
    assert max(out["logit_gaps"]) <= chip_smoke.GDN_LOGIT_TOL
    assert min(out["rows_compared"]) >= 4
    assert "decode_step" in out["kernels"]
    assert "skipped" in chip_smoke.conv_phase(
        _tiny_sizes(), jax.devices()[:1], False, chip_smoke.CompileClock())


def test_smoke_gates_fail_loudly():
    """The checks that tell a chip run from a CPU or interpreter run."""
    with pytest.raises(chip_smoke.SmokeFailure, match="XLA composition"):
        chip_smoke.attention_route("func.func @main() { stablehlo.dot }",
                                   True, "step")
    text = ('%1 = stablehlo.custom_call @tpu_custom_call(%0) '
            '{backend_config = "...", kernel_name = "_fwd_kernel"}')
    assert chip_smoke.attention_route(text, True, "step") == \
        {"_fwd_kernel": 1}
    # a mixed tick's program: tiles through _prefill_kernel, single-token
    # rows through the decode walk, never _kernel or the XLA read
    good = {"decode_step": {"_decode_kernel": 1},
            "prefill_T8_tiled": {"_decode_kernel": 1},
            "prefill_T1032_tiled": {"_decode_kernel": 1,
                                    "_prefill_kernel": 1}}
    chip_smoke.check_put_routes(good, 8, True)
    chip_smoke.check_put_routes({"prefill_T64": {"_kernel": 1}}, 8, True)
    for bad in ({"prefill_T1032_tiled": {"_kernel": 1}},
                {"prefill_T1032_tiled": {"_decode_kernel": 1}},
                {"prefill_T1032_tiled": {"_prefill_kernel": 1, "_kernel": 1}},
                {"prefill_T8_tiled": {"_kernel": 1}}):
        with pytest.raises(chip_smoke.SmokeFailure, match="token-grid"):
            chip_smoke.check_put_routes({**good, **bad}, 8, True)
    for bad in ({"prefill_T8_tiled": {}},
                {"prefill_T1032_tiled": {"_prefill_kernel": 1}}):
        with pytest.raises(chip_smoke.SmokeFailure, match="decode walk"):
            chip_smoke.check_put_routes({**good, **bad}, 8, True)
    with pytest.raises(chip_smoke.SmokeFailure, match="untiled"):
        chip_smoke.check_put_routes({**good, "prefill_T16": {}}, 8, False)
    # everything on the first device, or replicated everywhere, fails
    devs = jax.devices()[:2]
    one = {"w": jax.device_put(jnp.ones((64, 64)), devs[0])}
    with pytest.raises(chip_smoke.SmokeFailure, match="no shard"):
        chip_smoke.shard_report(one, devs, "state")
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    mesh = Mesh(devs, ("model",))
    rep = {"w": jax.device_put(jnp.ones((64, 64)), NamedSharding(mesh, P()))}
    with pytest.raises(chip_smoke.SmokeFailure, match="replicated"):
        chip_smoke.shard_report(rep, devs, "state")
    split = {"w": jax.device_put(jnp.ones((64, 64)),
                                 NamedSharding(mesh, P("model")))}
    assert chip_smoke.shard_report(split, devs, "state")[
        "per_device_bytes"] == [8192, 8192]


def test_cli_refuses_to_run_without_a_chip():
    """``python chip_smoke.py`` on the CPU: non-zero within seconds, naming
    the platform it found, and no result line."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    r = subprocess.run([sys.executable, os.path.join(_REPO, "chip_smoke.py")],
                       env=env, capture_output=True, text=True, timeout=120)
    assert r.returncode != 0
    assert "platform=cpu" in r.stdout
    assert "no TPU" in r.stderr and "'cpu'" in r.stderr
    for line in r.stdout.splitlines():
        assert not line.startswith("{"), line     # no JSON result


def test_on_tpu_lets_backend_errors_propagate(monkeypatch):
    """A backend that fails to start is an error, not 'not a TPU'."""
    from deepspeed_tpu.utils.platform import on_tpu

    assert on_tpu() is False                      # the CPU mesh

    def broken(*_a, **_k):
        raise RuntimeError("Unable to initialize backend 'tpu'")

    monkeypatch.setattr(jax, "devices", broken)
    with pytest.raises(RuntimeError, match="Unable to initialize"):
        on_tpu()
    from deepspeed_tpu.accelerator import real_accelerator

    monkeypatch.delenv("DS_ACCELERATOR", raising=False)
    with pytest.raises(RuntimeError, match="Unable to initialize"):
        real_accelerator._detect()


def test_compile_cache_is_placeable_from_outside(monkeypatch):
    from deepspeed_tpu.utils.compile_cache import enable_compile_cache

    updates = []
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: updates.append((k, v)))
    # placed from outside: JAX reads the variable itself, code sets nothing
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
    assert enable_compile_cache() == "/somewhere/else"
    assert updates == []
    # not placed: a fixed path under the checkout, never a temporary one
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    want = os.path.join(_REPO, ".jax_cache")
    assert enable_compile_cache() == want
    assert updates == [("jax_compilation_cache_dir", want)]


def test_one_process_per_chip_refusals(monkeypatch, tmp_path):
    """On a TPU host nothing starts several chip-needing children: the
    launcher and the fleet's worker mode raise instead of hanging on a held
    chip, and the autotuner refuses ``isolate``."""
    from deepspeed_tpu.utils import platform

    monkeypatch.setattr(platform, "tpu_host", lambda: True)
    with pytest.raises(RuntimeError, match="One process drives all local"):
        platform.refuse_chip_children(2, {}, "launcher")
    platform.refuse_chip_children(1, {}, "launcher")
    platform.refuse_chip_children(2, {"JAX_PLATFORMS": "cpu"}, "launcher")

    from deepspeed_tpu.fleet.worker import FleetFrontEnd

    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    with pytest.raises(RuntimeError, match="FleetFrontEnd worker mode"):
        FleetFrontEnd(lambda name, spool: [sys.executable, "-c", "pass"],
                      n_replicas=2, run_dir=str(tmp_path))

    from deepspeed_tpu.autotuning.autotuner import Autotuner

    monkeypatch.setattr(platform, "on_tpu", lambda: True)
    tuner = Autotuner(model=None, base_config={},
                      sample_batch_fn=lambda n: (), isolate=True,
                      results_dir=str(tmp_path / "results"))
    with pytest.raises(RuntimeError, match="isolate=True"):
        tuner.tune()
    assert not (tmp_path / "results").exists()
