"""PR 39: are the accepted serving families' step programs the parent's?
(A model without ``kv_groups`` must compute what it computed before the
state manager had two groups.)  Traces ``decode_step`` and three two-segment
``put`` programs of each of five families (Mistral, OLMoE, Qwen3-Next,
Moonlight, LFM2) at small widths that
keep the kernels' routes (heads of 128, the chip's routes forced on the
CPU), and writes each program's jaxpr, Pallas kernel bodies included, source
locations stripped, to a file a program:

    python3 benchmark/tools/calls/pr39_jaxprs.py <checkout> <out dir>

Run it on ``git archive`` of the parent and on the change, then ``diff -r``
the two directories.  (The LOWERED text differs wherever a line moved in a
file that holds a kernel: a Mosaic call's serialized body carries source
locations.  The jaxprs are what was computed.)"""
import os, sys, json
root, out = sys.argv[1], sys.argv[2]
sys.path.insert(0, root)
os.environ["JAX_PLATFORMS"] = "cpu"
import jax, jax.numpy as jnp
from deepspeed_tpu.inference.v2.model_implementations import ragged_llama
from deepspeed_tpu.inference.v2.kernels import blocked_flash
import deepspeed_tpu.inference.v2.kernels.latent_flash as lf
import deepspeed_tpu.utils.platform as plat
ragged_llama.on_tpu = lambda: True
blocked_flash.on_tpu = lambda: True
lf.on_tpu = lambda: True
from deepspeed_tpu.inference.v2 import InferenceEngineV2, RaggedInferenceEngineConfig
from deepspeed_tpu.inference.v2.ragged.ragged_wrapper import packed_length
from benchmark.lib import spec
os.makedirs(out, exist_ok=True)
CELLS = {
 "mistral": ("mistral-7b-v0.1-serve-1chip", {"hidden_size": 256, "intermediate_size": 512, "num_attention_heads": 2, "num_key_value_heads": 1, "num_hidden_layers": 2, "vocab_size": 512}),
 "olmoe": ("olmoe-1b-7b-0125-serve-1chip", {"hidden_size": 256, "intermediate_size": 128, "num_attention_heads": 2, "num_key_value_heads": 2, "num_hidden_layers": 2, "vocab_size": 512, "num_experts": 8}),
 "qwen3next": ("qwen3-next-80b-a3b-serve-1chip", {"num_experts_per_tok": 3, "hidden_size": 256, "num_hidden_layers": 4, "vocab_size": 512, "num_attention_heads": 2, "num_key_value_heads": 1, "head_dim": 128, "linear_num_key_heads": 2, "linear_num_value_heads": 4, "num_experts": 4, "router_experts": 8, "moe_intermediate_size": 128, "shared_expert_intermediate_size": 128}),
 "lfm2": ("lfm2-24b-a2b-serve-1chip", {"hidden_size": 256, "intermediate_size": 512, "moe_intermediate_size": 128, "num_attention_heads": 4, "num_key_value_heads": 2, "num_hidden_layers": 4, "layer_types": ["conv", "conv", "full_attention", "conv"], "vocab_size": 512, "num_experts": 8}),
 "moonlight": ("moonlight-16b-a3b-serve-1chip", {"num_experts_per_tok": 3, "hidden_size": 256, "intermediate_size": 512, "moe_intermediate_size": 128, "num_attention_heads": 2, "num_hidden_layers": 3, "vocab_size": 512, "n_routed_experts": 4, "router_experts": 8}),
}
b = spec.benchmark_spec()
for name, (cfgname, over) in CELLS.items():
    entry = [c for c in b["configs"] if c["name"] == cfgname][0]
    cfg = spec.load_json(os.path.join(root, entry["file"]))
    cfg.update(over)
    family = spec.module("families", cfg["family"])
    params = jax.tree.map(lambda l: jax.ShapeDtypeStruct(l.shape, jnp.bfloat16), family.serve_param_shapes(cfg))
    S, budget, bs, ctx = 8, 256, 128, 1024
    eng = InferenceEngineV2(family.serve_model(cfg, bs), params, RaggedInferenceEngineConfig.from_dict({
        "state_manager": {"max_ragged_batch_size": budget, "max_ragged_sequence_count": S, "max_context": ctx},
        "kv_cache": {"block_size": bs, "num_blocks": 40}}))
    B = ctx // bs
    ints = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)
    cache = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), eng.state_manager.kv_cache.cache)
    st = (ints(S),) if eng._stateful else ()
    progs = {"decode_step": (eng._get_decode_step(), (ints(S, B), ints(S), ints(S)) + st)}
    for key in (S, S + 128, S + 256):
        progs[f"T{key}"] = (eng._get_step(key, eng.PREFILL_TILE), (ints(packed_length(key, S, B, eng._stateful)),))
    for pname, (fn, args) in progs.items():
        import re
        txt = str(fn.trace(params, cache, *args).jaxpr)
        txt = re.sub(r" at [^ \n]*\.py:\d+", "", txt)
        txt = re.sub(r"/root/(scratch/parent|repo)/", "", txt)
        open(os.path.join(out, f"{name}.{pname}.txt"), "w").write(txt)
        print(name, pname, len(txt))
