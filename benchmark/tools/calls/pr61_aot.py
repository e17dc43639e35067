"""PR 61: the dots3-note cell's step programs at real size for a described
v5e, no chip (``pr50_aot.py``'s way: two-segment ragged batches of
``max_seqs`` one-token rows and 128 x 2^k tile rows, and ``decode_step``) for
an engine with TWO pools of two row widths: the global layers' leaves at
``kv_pool_blocks``, the window layers' as the state manager sized them.
What it is for: whether the banded walk lowers at 64 heads against a
1,152-lane row, whether ``sparse_tile_read`` and the grouped GEMM do at 128
heads / 32 experts of 1,536, and whether 8.17 GB of weights + both pools +
the largest program's temporaries stay under the chip's 16 GB.

    JAX_PLATFORMS=cpu python3 benchmark/tools/calls/pr61_aot.py [key=value ...] [rows ...]
"""
import os
import sys
import time

_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
sys.path.insert(0, _ROOT)

from benchmark.tools import aot                         # noqa: E402  (env)

import jax                                              # noqa: E402
import jax.numpy as jnp                                 # noqa: E402
import numpy as np                                      # noqa: E402

from benchmark.lib import spec                          # noqa: E402

KERNELS = ("_latent_decode_kernel", "_sparse_tile_read_kernel",
           "_gmm_kernel")


def main(*args, name="dots3-note-prev-serve-1chip"):
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from deepspeed_tpu.inference.v2 import (InferenceEngineV2,
                                            RaggedInferenceEngineConfig)

    cfg, _mix, _chips = aot._config(name, [a for a in args if "=" in a])
    tiles = [a for a in args if "=" not in a]
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
                "max_ragged_sequence_count":
                    sv["max_ragged_sequence_count"],
                "max_context": sv["max_context"]},
            "kv_cache": {"block_size": sv["block_size"], "num_blocks": 4}}))
    kv = engine.state_manager.kv_cache
    rows = int(sv["kv_pool_blocks"]) * int(sv["block_size"])
    cache = {
        layer: jax.tree.map(
            lambda a, w=int(layer.split("_")[1]) in kv.window_layers:
            jax.ShapeDtypeStruct(((a.shape[0] if w else rows),)
                                 + a.shape[1:], a.dtype, sharding=one),
            leaves) for layer, leaves in kv.cache.items()}
    nbytes = lambda tree: sum(
        int(np.prod(l.shape)) * jnp.dtype(l.dtype).itemsize
        for l in jax.tree_util.tree_leaves(tree))
    resident = nbytes(params) + nbytes(cache)
    print(f"{name}: weights {nbytes(params) / 1e9:.2f} GB, global pool "
          f"{rows} tokens + window pool {kv.window_blocks} blocks = "
          f"{nbytes(cache) / 1e9:.2f} GB", flush=True)
    S = int(sv["max_ragged_sequence_count"])
    B = -(-int(sv["max_context"]) // int(sv["block_size"]))
    ints = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one)
    tile = engine._prefill_tile()
    sizes = [int(t) for t in tiles] or [
        tile << i for i in range(12) if tile << i <= int(sv["token_budget"])]
    real_devices = jax.devices
    jax.devices = lambda *a, **k: list(topo.devices)[:1]
    try:
        for key in ["decode_step"] + [(S + t, tile) for t in sizes]:
            t0 = time.time()
            if key == "decode_step":
                fn = engine._get_decode_step()
                step_args = (ints(S, B), ints(S), ints(S), ints(S, B))
            else:
                fn = engine._get_step(*key)
                # token ids, slots, positions, both write targets a row;
                # both tables; context lengths and logits rows a sequence
                step_args = (ints(5 * key[0] + 2 * S * B + 2 * S),)
            lowered = fn.lower(params, cache, *step_args)
            compiled = lowered.compile()
            aot._report(f"  {key}", compiled, resident, t0)
            text = compiled.as_text()
            print("    kernels: " + ", ".join(
                f"{k} x {text.count(k)}" for k in KERNELS),
                flush=True)
    finally:
        jax.devices = real_devices


if __name__ == "__main__":
    main(*sys.argv[1:])
