"""PR 41, chip calls 10-: where does the engine part of ``chip_smoke.py``'s ``moe`` phase (OLMoE widths, depth 2, 8 slots,
a pool of 8 blocks = 4 MB a leaf) stop answering?  The phase builds a grouped engine, serves 300 + 4 tokens, deletes it,
builds the dense oracle and calls ``put``: that ``put`` never returned on the change (calls 6-9; call 9 showed that holding
the kernels' pool operands to HBM by ``pltpu.with_memory_space_constraint`` changes nothing: both kernels, the walk alone,
the tiled kernel alone, none: five of five stalled, always in the second engine's ``put``).  One variant a process:

    python3 tools/chip_calls/pr41_hang_probe.py <variant> [blocks=N]

``base`` grouped then dense (the phase); ``dense_first`` the dense oracle alone; ``grouped_twice``; ``xla_attn`` grouped
then dense with the dense engine's attention on the XLA reads; ``insert3d`` grouped then dense with the dense engine's row
insert through the ``[rows, Hkv, D]`` view of the pool (the parent's scatter); ``plain_no_kernel`` the dense oracle twice
with its attention on the XLA reads and the row insert ``pool.at[kv_dest].set(rows)`` (what the tree had in calls 6-10): a
process that runs no Pallas kernel at all.  A marker line before and after every call;
faulthandler says where Python waits after 70 s; the caller kills the process.
"""
import faulthandler
import sys
import time

sys.path.insert(0, ".")
faulthandler.dump_traceback_later(70, exit=False)
variant = sys.argv[1]
blocks = [int(a.split("=")[1]) for a in sys.argv[2:] if a.startswith("blocks=")]

import jax                                                   # noqa: E402
import numpy as np                                           # noqa: E402

import chip_smoke                                            # noqa: E402
from deepspeed_tpu.inference.v2 import InferenceEngineV2, RaggedInferenceEngineConfig  # noqa: E402
from deepspeed_tpu.inference.v2.model_implementations import ragged_llama  # noqa: E402
from deepspeed_tpu.inference.v2.model_implementations.ragged_mixtral import RaggedMixtral  # noqa: E402
from deepspeed_tpu.models.mixtral import MixtralForCausalLM  # noqa: E402

T0 = time.time()


def say(*a):
    print(f"PROBE {variant} +{time.time() - T0:.1f}s", *a, flush=True)


class DenseOracle(RaggedMixtral):
    grouped = False


sizes = chip_smoke.chip_sizes(1)
cfg, bs = sizes.moe_config, sizes.block_size
params = chip_smoke._seeded_bf16_params(cfg, model_cls=MixtralForCausalLM)
n_prompt, n_new = sizes.moe_prompt_len, sizes.moe_new_tokens
max_context = -(-(n_prompt + n_new + 1) // bs) * bs
eng_cfg = RaggedInferenceEngineConfig.from_dict({
    "state_manager": {"max_ragged_batch_size": sizes.token_budget, "max_ragged_sequence_count": sizes.max_seqs,
                      "max_context": max_context},
    "kv_cache": {"block_size": bs, "num_blocks": blocks[0] if blocks else 2 * (max_context // bs) + 2}})
ids = np.random.default_rng(0).integers(0, cfg.vocab_size, size=(n_prompt + n_new,))
order = {"base": ("grouped", "dense"), "dense_first": ("dense",), "grouped_twice": ("grouped", "grouped"),
         "xla_attn": ("grouped", "dense"), "insert3d": ("grouped", "dense"), "plain_no_kernel": ("dense", "dense")}[variant]
if variant == "plain_no_kernel":
    ragged_llama.on_tpu = lambda: False

    def plain(layer_cache, kv_dest, k, v):
        flat = lambda x: x.reshape(x.shape[0], -1)
        return (layer_cache["k"].at[kv_dest].set(flat(k).astype(layer_cache["k"].dtype)),
                layer_cache["v"].at[kv_dest].set(flat(v).astype(layer_cache["v"].dtype)))
    ragged_llama.insert_kv = plain
say("params ready; engines", order)
for n, path in enumerate(order):
    if n == 1 and variant == "xla_attn":
        ragged_llama.on_tpu = lambda: False
    if n == 1 and variant == "insert3d":
        def insert3d(layer_cache, kv_dest, k, v):
            def put(pool, x):
                return pool.reshape(pool.shape[0], *x.shape[1:]).at[kv_dest].set(x.astype(pool.dtype)).reshape(pool.shape)
            return put(layer_cache["k"], k), put(layer_cache["v"], v)
        ragged_llama.insert_kv = insert3d
    engine = InferenceEngineV2({"grouped": RaggedMixtral, "dense": DenseOracle}[path](cfg, bs), params, eng_cfg)
    say(n, path, "put ...")
    row = np.asarray(engine.put([1], [ids[:n_prompt].tolist()])[1], np.float32)
    say(n, path, "put done", float(np.abs(row).max()))
    for t in ids[n_prompt:]:
        row = np.asarray(jax.device_get(engine.decode_step([1], [int(t)])), np.float32)[0]
    say(n, path, "decode steps done", float(np.abs(row).max()))
    del engine
say("DONE")
