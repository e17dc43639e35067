"""PR 40: are the step programs the parent's?  ``put`` became ``prepare`` +
``launch``; no step program was to change.  Builds every two-segment
``ragged_step_T*`` program (and ``decode_step``) of six serving families
(Mistral, OLMoE, Qwen3-Next, Moonlight, LFM2, Trinity) at small widths that
keep the kernels' routes (heads of 128, the chip's routes forced on the CPU,
lowered for the TPU), and writes each program's LOWERED text and its jaxpr
(Pallas kernel bodies included, source locations stripped) to a file a
program:

    python3 tools/chip_calls/pr40_program_text.py <checkout> <out dir>

Run it on ``git archive`` of the parent and on the change, then ``diff -r``
the two directories: no output is the acceptance.  (The form of
``benchmark/tools/calls/pr39_jaxprs.py``.)  The lowered text is compared
byte for byte but for the serialized body of each Mosaic call, which is cut
out: it carries the Python call stack of the trace, and with it the line
numbers of ``engine_v2.py``, where ``_get_step`` moved down the file (first
reading of this script, PR 40: every ``*.lowered.txt`` of a family with a
Mosaic kernel differed in those bytes alone, with both trees read through
one symlinked path).  What the kernels compute is in the jaxpr file, their
bodies included.  No chip, no value, no time."""
import os
import re
import sys

root, out = os.path.abspath(sys.argv[1]), sys.argv[2]
sys.path.insert(0, root)
os.environ["JAX_PLATFORMS"] = "cpu"
import jax                                                  # noqa: E402
import jax.numpy as jnp                                     # noqa: E402

from deepspeed_tpu.inference.v2.kernels import blocked_flash  # noqa: E402
import deepspeed_tpu.inference.v2.kernels.latent_flash as lf  # noqa: E402
from deepspeed_tpu.inference.v2.model_implementations import (  # noqa: E402
    ragged_llama)

ragged_llama.on_tpu = lambda: True
blocked_flash.on_tpu = lambda: True
lf.on_tpu = lambda: True
from benchmark.lib import spec                              # noqa: E402
from deepspeed_tpu.inference.v2 import (                    # noqa: E402
    InferenceEngineV2, RaggedInferenceEngineConfig)
from deepspeed_tpu.inference.v2.ragged.ragged_wrapper import (  # noqa: E402
    packed_length)

os.makedirs(out, exist_ok=True)
CELLS = {
    "mistral": ("mistral-7b-v0.1-serve-1chip", {
        "hidden_size": 256, "intermediate_size": 512,
        "num_attention_heads": 2, "num_key_value_heads": 1,
        "num_hidden_layers": 2, "vocab_size": 512}),
    "olmoe": ("olmoe-1b-7b-0125-serve-1chip", {
        "hidden_size": 256, "intermediate_size": 128,
        "num_attention_heads": 2, "num_key_value_heads": 2,
        "num_hidden_layers": 2, "vocab_size": 512, "num_experts": 8}),
    "qwen3next": ("qwen3-next-80b-a3b-serve-1chip", {
        "num_experts_per_tok": 3, "hidden_size": 256,
        "num_hidden_layers": 4, "vocab_size": 512,
        "num_attention_heads": 2, "num_key_value_heads": 1, "head_dim": 128,
        "linear_num_key_heads": 2, "linear_num_value_heads": 4,
        "num_experts": 4, "router_experts": 8, "moe_intermediate_size": 128,
        "shared_expert_intermediate_size": 128}),
    "lfm2": ("lfm2-24b-a2b-serve-1chip", {
        "hidden_size": 256, "intermediate_size": 512,
        "moe_intermediate_size": 128, "num_attention_heads": 4,
        "num_key_value_heads": 2, "num_hidden_layers": 4,
        "layer_types": ["conv", "conv", "full_attention", "conv"],
        "vocab_size": 512, "num_experts": 8}),
    "moonlight": ("moonlight-16b-a3b-serve-1chip", {
        "num_experts_per_tok": 3, "hidden_size": 256,
        "intermediate_size": 512, "moe_intermediate_size": 128,
        "num_attention_heads": 2, "num_hidden_layers": 3, "vocab_size": 512,
        "n_routed_experts": 4, "router_experts": 8}),
    "trinity": ("trinity-large-preview-serve-1chip", {
        "hidden_size": 256, "intermediate_size": 512,
        "moe_intermediate_size": 128, "num_attention_heads": 2,
        "num_key_value_heads": 1, "num_hidden_layers": 3,
        "layer_types": ["sliding_attention", "full_attention",
                        "sliding_attention"],
        "sliding_window": 256, "vocab_size": 512, "num_experts": 4,
        "router_experts": 8, "num_experts_per_tok": 2}),
}
b = spec.benchmark_spec()
for name, (cfgname, over) in CELLS.items():
    entry = [c for c in b["configs"] if c["name"] == cfgname][0]
    cfg = spec.load_json(os.path.join(root, entry["file"]))
    cfg.update(over)
    family = spec.module("families", cfg["family"])
    params = jax.tree.map(
        lambda l: jax.ShapeDtypeStruct(l.shape, jnp.bfloat16),
        family.serve_param_shapes(cfg))
    S, budget, bs, ctx = 8, 256, 128, 1024
    eng = InferenceEngineV2(
        family.serve_model(cfg, bs), params,
        RaggedInferenceEngineConfig.from_dict({
            "state_manager": {"max_ragged_batch_size": budget,
                              "max_ragged_sequence_count": S,
                              "max_context": ctx},
            "kv_cache": {"block_size": bs, "num_blocks": 40}}))
    B = ctx // bs
    grouped = getattr(eng, "_grouped", False)

    def ints(*s):
        return jax.ShapeDtypeStruct(s, jnp.int32)

    cache = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                         eng.state_manager.kv_cache.cache)
    extra = ((ints(S),) if eng._stateful else ()) \
        + ((ints(S, B),) if grouped else ())
    progs = {"decode_step": (eng._get_decode_step(),
                             (ints(S, B), ints(S), ints(S)) + extra)}
    for key in (S, S + 128, S + 256):
        fn = eng._get_step(key, eng.PREFILL_TILE)
        progs[fn.__name__] = (fn, (ints(packed_length(
            key, S, B, eng._stateful, **({"win": True} if grouped else {}))),))
    for pname, (fn, args) in progs.items():
        traced = fn.trace(params, cache, *args)
        jaxpr = re.sub(r" at [^ \n]*\.py:\d+", "", str(traced.jaxpr))
        jaxpr = re.sub(re.escape(root) + "/", "", jaxpr)
        lowered = re.sub(
            r'(\\22body\\22: \\22)[^\\]*', r"\1(cut)",
            traced.lower(lowering_platforms=("tpu",)).as_text())
        for kind, txt in (("jaxpr", jaxpr), ("lowered", lowered)):
            with open(os.path.join(out, f"{name}.{pname}.{kind}.txt"),
                      "w") as f:
                f.write(txt)
        print(name, pname, len(jaxpr), len(lowered), flush=True)
