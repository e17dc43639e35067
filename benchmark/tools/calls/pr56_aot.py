"""PR 56: the Olmo-Hybrid cell's step programs at real size for a described
v5e, no chip (``pr52_aot.py``'s way for an engine with STATE SLOTS beside
its KV pool: the state leaves keep their ``[slots + 1, ...]`` shapes, the
KV leaves take the configuration's pool rows, and ``decode_step`` takes the
rows' state slots), with XLA's ``memory_analysis()`` of each.  What it is
for here: whether ``_gdn_step_kernel`` and ``_gdn_chunk_kernel`` lower and
fit at 30 heads of 96 x 192, whether ``_decode_kernel`` and
``_prefill_kernel`` do at 30 KV heads of 128, and whether weights + both
pools + the largest program's temporaries stay under the chip's 16 GB.

    JAX_PLATFORMS=cpu python3 benchmark/tools/calls/pr56_aot.py [config] [key=value ...] [rows ...]
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

KERNELS = ("_gdn_step_kernel", "_gdn_chunk_kernel", "_decode_kernel",
           "_prefill_kernel")


def main(name="olmo-hybrid-7b-serve-1chip", *args):
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from deepspeed_tpu.inference.v2 import (InferenceEngineV2,
                                            RaggedInferenceEngineConfig)
    from deepspeed_tpu.inference.v2.ragged.ragged_wrapper import \
        packed_length

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
    rows = int(sv["kv_pool_blocks"]) * int(sv["block_size"])
    cache = {layer: {
        leaf: jax.ShapeDtypeStruct(
            ((rows,) + a.shape[1:]) if leaf in ("k", "v") else a.shape,
            a.dtype, sharding=one) for leaf, a in leaves.items()}
        for layer, leaves in engine.state_manager.kv_cache.cache.items()}
    nbytes = lambda tree: sum(
        int(np.prod(l.shape)) * jnp.dtype(l.dtype).itemsize
        for l in jax.tree_util.tree_leaves(tree))
    pool = engine.state_manager.state_pool
    kv = {k: {n: a for n, a in v.items() if n in ("k", "v")}
          for k, v in cache.items()}
    resident = nbytes(params) + nbytes(kv) + pool.total_bytes
    print(f"{name}: weights {nbytes(params) / 1e9:.2f} GB, KV pool {rows} "
          f"tokens = {nbytes(kv) / 1e9:.2f} GB, state pool "
          f"{pool.num_slots + 1} slots x {pool.per_sequence_bytes} B = "
          f"{pool.total_bytes / 1e9:.2f} GB as the chip holds it", flush=True)
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
                args = (ints(S, B), ints(S), ints(S), ints(S))
            else:
                fn = engine._get_step(*key)
                args = (ints(packed_length(key[0], S, B, state=True)),)
            compiled = fn.lower(params, cache, *args).compile()
            aot._report(f"  {key}", compiled, resident, t0)
            text = compiled.as_text()
            print("    kernels: " + ", ".join(
                f"{k} x {text.count(k)}" for k in KERNELS), flush=True)
    finally:
        jax.devices = real_devices


if __name__ == "__main__":
    main(*sys.argv[1:])
