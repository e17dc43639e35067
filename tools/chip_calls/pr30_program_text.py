"""PR 30: which attention kernel each serving program calls, and will the
chip's compiler take it?  ``pr27_program_text.py``'s lowering (the cells'
real sizes for a described v5e, no chip) of ``decode_step`` and two tiled
``put`` programs of the three serving configurations of ``<tree>``: the
report's hash line, the Mosaic kernels in the lowered text, the count of
lines under the scope ``attn/dense_read`` that are an XLA ``dot_general``
(the dense read's; 0 = the scope holds the decode walk only), and, with
``compile`` as a second argument, the compiled program's temporaries and a
count of whole-pool ``copy`` / ``reshape`` instructions in front of the
Mosaic calls.  Nothing runs: no value, no time.

    JAX_PLATFORMS=cpu python3 tools/chip_calls/pr30_program_text.py <tree> [compile|lower [config ...]]
"""

import os
import re
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import pr27_program_text as text        # noqa: E402  (chdir's into <tree>)

COMPILE = len(sys.argv) > 2 and sys.argv[2] == "compile"


def report(name, lowered):
    text.report(name, lowered)
    dbg = lowered.as_text(debug_info=True)
    kernels = re.findall(r'@tpu_custom_call\(.*?kernel_name = "([^"]+)"',
                         dbg)
    dense_dots = len(re.findall(
        r'loc\("[^"]*attn/dense_read/dot_general', dbg))
    print(f"    kernels {sorted(set(kernels))}; attn/dense_read XLA dots "
          f"{dense_dots}", flush=True)
    if COMPILE:
        compiled = lowered.compile()
        mem = compiled.memory_analysis()
        pool = re.compile(r"= bf16\[(\d+),128,[^\]]*\]\S* (copy|reshape)\(")
        moved = [m.group(0) for m in pool.finditer(compiled.as_text())
                 if int(m.group(1)) >= 160]
        print(f"    compiled: temporaries {mem.temp_size_in_bytes / 1e6:.1f} "
              f"MB; whole-pool copy/reshape instructions {len(moved)} "
              f"{moved[:2]}", flush=True)


def serve(config):
    """``pr27_program_text.serve`` for an engine that may carry recurrent
    state beside its KV pool (Qwen3-Next: the state leaves keep their
    shapes, the decode step takes each row's state slot, the packed
    metadata row is longer)."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from benchmark.lib import spec
    from benchmark.tools import aot
    from deepspeed_tpu.inference.v2 import (InferenceEngineV2,
                                            RaggedInferenceEngineConfig)
    from deepspeed_tpu.inference.v2.ragged.ragged_wrapper import (
        packed_length)

    cfg, _mix, _chips = aot._config(config, [])
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    family = spec.module("families", cfg["family"])
    sv = cfg["serve"]
    params = jax.tree.map(
        lambda l: jax.ShapeDtypeStruct(l.shape, jnp.bfloat16, sharding=one),
        family.serve_param_shapes(cfg))
    engine = InferenceEngineV2(
        family.serve_model(cfg, int(sv["block_size"])), params,
        RaggedInferenceEngineConfig.from_dict({
            "state_manager": {
                "max_ragged_batch_size": sv["token_budget"],
                "max_ragged_sequence_count": sv["max_ragged_sequence_count"],
                "max_context": sv["max_context"]},
            "kv_cache": {"block_size": sv["block_size"], "num_blocks": 4}}))
    rows = int(sv["kv_pool_blocks"]) * int(sv["block_size"])

    def leaf(path, a):
        kv = str(getattr(path[-1], "key", "")) in ("k", "v")
        return jax.ShapeDtypeStruct(
            ((rows,) + a.shape[1:]) if kv else a.shape, a.dtype,
            sharding=one)

    cache = jax.tree_util.tree_map_with_path(
        leaf, engine.state_manager.kv_cache.cache)
    S = int(sv["max_ragged_sequence_count"])
    B = -(-int(sv["max_context"]) // int(sv["block_size"]))
    ints = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one)
    state = (ints(S),) if engine._stateful else ()
    real_devices = jax.devices
    jax.devices = lambda *a, **k: list(topo.devices)[:1]
    try:
        report(f"{config} decode_step", engine._get_decode_step().lower(
            params, cache, ints(S, B), ints(S), ints(S), *state))
        for key in ((S, 128), (S + 256, 128), (S + 1024, 128)):
            report(f"{config} put {key}", engine._get_step(*key).lower(
                params, cache,
                ints(packed_length(key[0], S, B, engine._stateful))))
    finally:
        jax.devices = real_devices


if __name__ == "__main__":
    print(f"tree {text.tree}")
    for config in sys.argv[3:] or ("mistral-7b-v0.1-serve-1chip",
                                   "olmoe-1b-7b-0125-serve-1chip",
                                   "qwen3-next-80b-a3b-serve-1chip"):
        serve(config)
