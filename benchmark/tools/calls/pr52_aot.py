"""PR 52: the LongCat-Flash cell's step programs at real size for a
described v5e, no chip (``pr50_aot.py``'s way: two-segment ragged batches of
``max_seqs`` one-token rows and 128 x 2^k tile rows, and ``decode_step``),
with XLA's ``memory_analysis()`` of each.  What it is for here: whether the
three latent kernels lower and fit at 64 heads (``latent_expand``'s scratch
and its double-buffered ``W_kvb`` block under the 64 MB VMEM limit, the
prefill kernel's head loop of 8 groups), and whether weights + pool + the
largest program's temporaries stay under the chip's 16 GB.  A model with
``step_counters`` takes its decode tokens as ``int32[max_seqs + counters]``.

    JAX_PLATFORMS=cpu python3 benchmark/tools/calls/pr52_aot.py [config] [key=value ...] [rows ...]
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


def main(name="longcat-flash-omni-serve-1chip", *args):
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
    rows = int(sv["kv_pool_blocks"]) * int(sv["block_size"])
    cache = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct((rows,) + a.shape[1:], a.dtype,
                                       sharding=one),
        engine.state_manager.kv_cache.cache)
    nbytes = lambda tree: sum(
        int(np.prod(l.shape)) * jnp.dtype(l.dtype).itemsize
        for l in jax.tree_util.tree_leaves(tree))
    resident = nbytes(params) + nbytes(cache)
    print(f"{name}: weights {nbytes(params) / 1e9:.2f} GB, pool {rows} "
          f"tokens x {len(cache)} cache layers = {nbytes(cache) / 1e9:.2f} GB",
          flush=True)
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
                args = (ints(S, B), ints(S),
                        ints(S + len(engine.step_counters)))
            else:
                fn = engine._get_step(*key)
                args = (ints(4 * key[0] + S * B + 2 * S),)
            compiled = fn.lower(params, cache, *args).compile()
            aot._report(f"  {key}", compiled, resident, t0)
            text = compiled.as_text()
            print("    kernels: " + ", ".join(
                f"{k} x {text.count(k)}" for k in (
                    "_latent_decode_kernel", "_latent_expand_kernel",
                    "_latent_prefill_kernel", "_gmm_kernel")), flush=True)
    finally:
        jax.devices = real_devices


if __name__ == "__main__":
    main(*sys.argv[1:])
